"""Device time by ``jax.named_scope``, for the readers of the language
model's layers.

``lib/trace.Reduced`` keeps ``hlo_category`` alone, so this re-reads the
``.xplane.pb`` under ``run.trace_dir`` with ``lib/xplane.py``'s wire-format
functions and keeps, for every op on a device's ``XLA Ops`` line, every string
stat it carries (its own and its metadata's): whichever of them holds the
op's ``op_name`` (the scope path, e.g. ``.../layer_0/block/attn/attn_window/...``)
on this runtime, the scope's name is in it. An op belongs to the innermost of
the scopes below that its text names; the steady window is ``lib/trace``'s.
A program without these scopes, or a trace without a device, gives ``None``.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, NamedTuple, Optional

from . import trace, xplane

SCOPES = ("embed", "attn_proj", "attn_window", "attn_full", "moe_route",
          "moe_experts", "lm_head")
_SCOPE = re.compile(r"(?<![A-Za-z0-9_])(%s)(?![A-Za-z0-9_])" % "|".join(SCOPES))


def scope_of(text: str) -> Optional[str]:
    """The innermost (last named) of ``SCOPES`` in an op's text."""
    found = _SCOPE.findall(text)
    return found[-1] if found else None


class ScopeTimes(NamedTuple):
    steps: int
    seconds: Dict[str, float]   # device seconds per step by scope, first chip
    ops: Dict[str, Dict[str, float]]  # scope -> {op name: seconds per step}


def _device_ops(path: str):
    """(plane, line, name, text, start_ps, dur_ps) of every device event."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    for num, _, plane in xplane._fields(buf):
        if num != 1:
            continue
        name, lines, event_meta, stat_meta = "", [], [], []
        for n, _, v in xplane._fields(plane):
            if n == 2:
                name = xplane._text(v)
            elif n == 3:
                lines.append(v)
            elif n == 4:
                event_meta.append(v)
            elif n == 5:
                stat_meta.append(v)
        if not trace.is_device(name):
            continue
        stat_names: Dict[int, str] = {}
        for entry in stat_meta:
            key, val = xplane._map_entry(entry)
            for n, _, v in xplane._fields(val):
                if n == 2:
                    stat_names[key] = xplane._text(v)
        names: Dict[int, str] = {}
        texts: Dict[int, str] = {}
        for entry in event_meta:
            key, val = xplane._map_entry(entry)
            parts: List[str] = []
            for n, _, v in xplane._fields(val):
                if n == 2:
                    names[key] = xplane._text(v)
                elif n == 5:
                    s = xplane._stat(v, stat_names)[1]
                    if isinstance(s, str):
                        parts.append(s)
            texts[key] = " ".join(parts)
        for line in lines:
            line_name, t0_ns, events = "", 0, []
            for n, _, v in xplane._fields(line):
                if n == 2:
                    line_name = xplane._text(v)
                elif n == 3:
                    t0_ns = v
                elif n == 4:
                    events.append(v)
            if line_name not in (trace.OPS_LINE, trace.MODULES_LINE):
                continue
            for ev in events:
                meta = off = dur = 0
                own: List[str] = []
                for n, _, v in xplane._fields(ev):
                    if n == 1:
                        meta = v
                    elif n == 2:
                        off = v
                    elif n == 3:
                        dur = v
                    elif n == 4:
                        s = xplane._stat(v, stat_names)[1]
                        if isinstance(s, str):
                            own.append(s)
                text = " ".join([names.get(meta, ""), texts.get(meta, "")] + own)
                yield name, line_name, names.get(meta, ""), text, \
                    t0_ns * 1000 + off, dur


def read(trace_dir: Optional[str]) -> Optional[ScopeTimes]:
    files = sorted(glob.glob(os.path.join(
        trace_dir or "", "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return None
    ops = list(_device_ops(files[-1]))
    planes = sorted({o[0] for o in ops if o[1] == trace.OPS_LINE})
    if not planes:
        return None
    mine = [o for o in ops if o[0] == planes[0]]
    events = [trace.Event(p, line, name, None, start, dur)
              for p, line, name, _, start, dur in mine]
    win = trace.steady_window(events) or trace.whole_window(events)
    seconds: Dict[str, float] = collections.Counter()
    by_op: Dict[str, Dict[str, float]] = collections.defaultdict(
        collections.Counter)
    for _, line, name, text, start, dur in mine:
        if line != trace.OPS_LINE or not win.start_ps <= start < win.end_ps:
            continue
        scope = scope_of(text)
        if scope is not None:
            seconds[scope] += dur * trace.PS / win.steps
            by_op[scope][name] += dur * trace.PS / win.steps
    if not seconds:
        return None
    return ScopeTimes(win.steps, dict(seconds),
                      {s: dict(o) for s, o in by_op.items()})


def of_run(run) -> Optional[ScopeTimes]:
    """``read(run.trace_dir)``, once a run; its table goes to the log."""
    if not hasattr(run, "_scope_times"):
        run._scope_times = read(getattr(run, "trace_dir", None))
        if run._scope_times is not None:
            t = run._scope_times
            run.log(device_ms_per_step_by_scope={
                s: v * 1e3 for s, v in sorted(t.seconds.items())},
                longest_ops_by_scope={
                    s: sorted(((n, v * 1e3) for n, v in o.items()),
                              key=lambda kv: -kv[1])[:4]
                    for s, o in sorted(t.ops.items())})
    return run._scope_times


def scope_ms(run, scope: str) -> Optional[float]:
    t = of_run(run)
    return t.seconds[scope] * 1e3 if t is not None and scope in t.seconds else None


def counter_mean(run, name: str, traced: bool = False) -> Optional[float]:
    """Mean of a counter the step carries over the window's step records, or
    with ``traced`` over the traced steps' own (``run.traced_steps``, driver
    ``train_ref``): what a device time of the traced steps is set against."""
    steps = getattr(run, "traced_steps", ()) if traced else run.steps
    values = [r[name] for r in steps if r.get(name) is not None]
    return sum(values) / len(values) if values else None
