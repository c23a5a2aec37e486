"""Device time by ``jax.named_scope`` for scope names the caller gives.

``lib/scopes.py`` does this for the names in its constant ``SCOPES`` (the
first language model's); a reader of a later model's scopes hands its own
names here. Same trace, same wire-format walk (``scopes._device_ops``), same
steady window (``lib/trace``), same rule: an op belongs to the innermost (last
named) of the given names that its text holds. A program without these
scopes, or a trace without a device, gives ``None``.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Optional, Sequence

from . import scopes, trace

# the state-space hybrid's scopes (bigdl_tpu/nn/ssm.py, nn/decoder.py GatedMLP)
HYBRID_SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan", "mlp")


def _pattern(names: Sequence[str]):
    return re.compile(r"(?<![A-Za-z0-9_])(%s)(?![A-Za-z0-9_])" % "|".join(
        re.escape(n) for n in names))


def scope_of(text: str, names: Sequence[str]) -> Optional[str]:
    found = _pattern(names).findall(text)
    return found[-1] if found else None


def read(trace_dir: Optional[str], names: Sequence[str]
         ) -> Optional[scopes.ScopeTimes]:
    files = sorted(glob.glob(os.path.join(
        trace_dir or "", "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return None
    ops = list(scopes._device_ops(files[-1]))
    planes = sorted({o[0] for o in ops if o[1] == trace.OPS_LINE})
    if not planes:
        return None
    mine = [o for o in ops if o[0] == planes[0]]
    events = [trace.Event(p, line, name, None, start, dur)
              for p, line, name, _, start, dur in mine]
    win = trace.steady_window(events) or trace.whole_window(events)
    pattern = _pattern(names)
    seconds: Dict[str, float] = collections.Counter()
    by_op: Dict[str, Dict[str, float]] = collections.defaultdict(
        collections.Counter)
    for _, line, name, text, start, dur in mine:
        if line != trace.OPS_LINE or not win.start_ps <= start < win.end_ps:
            continue
        found = pattern.findall(text)
        if found:
            seconds[found[-1]] += dur * trace.PS / win.steps
            by_op[found[-1]][name] += dur * trace.PS / win.steps
    if not seconds:
        return None
    return scopes.ScopeTimes(win.steps, dict(seconds),
                             {s: dict(o) for s, o in by_op.items()})


def of_run(run, names: Sequence[str]) -> Optional[scopes.ScopeTimes]:
    """``read(run.trace_dir, names)``, once a run and set of names; its table
    goes to the log."""
    cache = run.__dict__.setdefault("_named_scope_times", {})
    key = tuple(names)
    if key not in cache:
        cache[key] = t = read(getattr(run, "trace_dir", None), names)
        if t is not None:
            run.log(device_ms_per_step_by_named_scope={
                s: v * 1e3 for s, v in sorted(t.seconds.items())},
                longest_ops_by_named_scope={
                    s: sorted(((n, v * 1e3) for n, v in o.items()),
                              key=lambda kv: -kv[1])[:4]
                    for s, o in sorted(t.ops.items())})
    return cache[key]


def scope_ms(run, scope: str, names: Sequence[str] = HYBRID_SCOPES
             ) -> Optional[float]:
    t = of_run(run, names)
    return t.seconds[scope] * 1e3 if t is not None and scope in t.seconds else None
