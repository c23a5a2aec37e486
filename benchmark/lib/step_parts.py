"""Device time a step by the parts of the step and by module.

Since PR 37 every op of a container-built model's train step carries, in its
name stack, the step part it belongs to (``PARTS``: scopes of
``optim/local_optimizer.py`` and ``parallel/distri_optimizer.py``) and the
module path from the containers' one seam (``nn/module.run_child``), e.g.
``jit(train_step_s1)/transpose(jvp(model_apply))/res2a/res2a_b1_conv/conv_general_dilated``.
This is ``lib/scope_times.read``'s walk (``scopes._device_ops``) and steady
window (``lib/trace``) with one more reduction:

* an op belongs to the LAST of ``PARTS`` its text holds, and to no part
  (``unowned``) when it holds none: the coverage of the tracing itself. The
  text also holds the op's source locations (``/.../nn/criterion.py:113``):
  only a word that is a path and does not start with ``/`` (a name stack:
  ``jit(train_step_s1)/...``) is searched, so a file is never a scope;
* it is backward when its text holds ``transpose(jvp(``, which JAX writes
  itself; a forward recomputed under ``nn.Remat`` sits inside the transposed
  program and counts as backward;
* its module is the path behind the part, less the primitive and less JAX's
  own words (``jvp(...)``, ``checkpoint``, ``while`` ...);
* an op that holds other ops (a ``while`` and its body's) counts its own time
  only, so the parts add up to the window's busy time.

A program without these scopes (the parent of PR 37), or a trace without a
device, gives ``None``, and a reader then reports nothing.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from . import scope_times, scopes, trace

PARTS = ("model_apply", "criterion", "optim_update", "param_views",
         "grad_exchange", "param_gather", "state_sync")
BACKWARD = "transpose(jvp("
UNOWNED = "unowned"
# path components that are JAX's own, not a module's
_STRUCTURAL = {"checkpoint", "rematted_computation", "while", "body", "cond",
               "closed_call", "remat", "pjit", "custom_jvp_call",
               "custom_vjp_call", "shard_map"}
_PART = scope_times._pattern(PARTS)
_TOKEN = re.compile(r"[^\s\"']+")
_OPCODE = re.compile(r"(?<=\s)([a-z][a-z0-9\-]*)\(")


class StepParts(NamedTuple):
    steps: int
    busy_s: float                       # own time of every op, per step
    parts: Dict[str, List[float]]       # part -> [forward s, backward s]
    modules: Dict[str, List[float]]     # module path -> [forward s, backward s]
    unowned_ops: Dict[str, float]       # op name -> s, ops with no part
    unowned_kinds: Dict[str, float]     # their opcode -> s


def opcode(name: str) -> str:
    """``copy-done`` of ``%copy-done.3 = f32[256]{0:T(256)} copy-done(...)``
    (this runtime names an op by its whole instruction); else the name."""
    found = _OPCODE.search(name.split(" = ", 1)[-1])
    return found.group(1) if found else name


def owner(text: str) -> Tuple[Optional[str], bool, str]:
    """(part, backward, module path) of an op's text."""
    for token in reversed(_TOKEN.findall(text)):
        # an op's name stack is a path that starts with its program's name
        # (``jit(train_step_s1)/...``); a source location starts with ``/``
        named = "/" in token and not token.startswith("/")
        hits = list(_PART.finditer(token)) if named else []
        if hits:
            break
    else:
        return None, False, ""
    part = hits[-1].group(1)
    # behind the last component that names the part, less the primitive
    tail = token[hits[-1].end():].split("/")[1:-1]
    path = "/".join(c for c in tail
                    if "(" not in c and ")" not in c and "->" not in c
                    and c not in _STRUCTURAL)
    if part != "model_apply":
        path = part + ("/" + path if path else "")
    return part, BACKWARD in text, path or part


def own_time(ops: Sequence[Tuple[int, int]]) -> List[int]:
    """Own picoseconds of ``(start, dur)`` events of one line: an event that
    holds later ones (a ``while`` over its body's ops) loses their time."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [d for _, d in ops]
    stack: List[int] = []
    for i in order:
        start, dur = ops[i]
        while stack and sum(ops[stack[-1]]) <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return own


def reduce(device_ops) -> Optional[StepParts]:
    """``device_ops``: ``(plane, line, name, text, start_ps, dur_ps)`` as
    ``scopes._device_ops`` yields them; the first chip's steady window."""
    ops = list(device_ops)
    planes = sorted({o[0] for o in ops if o[1] == trace.OPS_LINE})
    if not planes:
        return None
    mine = [o for o in ops if o[0] == planes[0]]
    events = [trace.Event(p, line, name, None, start, dur)
              for p, line, name, _, start, dur in mine]
    win = trace.steady_window(events) or trace.whole_window(events)
    inside = [o for o in mine if o[1] == trace.OPS_LINE
              and win.start_ps <= o[4] < win.end_ps]
    own = own_time([(o[4], o[5]) for o in inside])
    parts: Dict[str, List[float]] = collections.defaultdict(lambda: [0.0, 0.0])
    modules: Dict[str, List[float]] = collections.defaultdict(
        lambda: [0.0, 0.0])
    unowned: Dict[str, float] = collections.Counter()
    kinds: Dict[str, float] = collections.Counter()
    busy = 0.0
    for (_, _, name, text, _, _), ps in zip(inside, own):
        s = ps * trace.PS / win.steps
        busy += s
        part, backward, path = owner(text)
        if part is None:
            parts[UNOWNED][0] += s
            unowned[name] += s
            kinds[opcode(name)] += s
        else:
            parts[part][backward] += s
            modules[path][backward] += s
    if not any(p in parts for p in PARTS):
        return None
    return StepParts(win.steps, busy, dict(parts), dict(modules),
                     dict(unowned), dict(kinds))


def read(trace_dir: Optional[str]) -> Optional[StepParts]:
    files = sorted(glob.glob(os.path.join(
        trace_dir or "", "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return None
    return reduce(scopes._device_ops(files[-1]))


def of_run(run) -> Optional[StepParts]:
    """``read(run.trace_dir)``, once a run; its tables go to the log: what
    the next look at a convolutional model's step starts from."""
    if "_step_parts" not in run.__dict__:
        run._step_parts = t = read(getattr(run, "trace_dir", None))
        if t is not None:
            ms = lambda v: [round(x * 1e3, 4) for x in v]  # noqa: E731
            longest = sorted(t.modules.items(), key=lambda kv: -sum(kv[1]))
            run.log(
                traced_steps=t.steps, device_busy_ms_per_step=t.busy_s * 1e3,
                device_ms_per_step_by_part={
                    p: ms(v) for p, v in sorted(t.parts.items())},
                device_ms_per_step_by_module=[
                    [path] + ms(v) for path, v in longest[:24]],
                unowned_ms_per_step_by_opcode={
                    k: round(v * 1e3, 4) for k, v in sorted(
                        t.unowned_kinds.items(), key=lambda kv: -kv[1])[:8]},
                longest_unowned_ops=[
                    [n[:160], round(v * 1e3, 4)] for n, v in sorted(
                        t.unowned_ops.items(), key=lambda kv: -kv[1])[:4]])
    return run._step_parts


def part_ms(run, parts: Sequence[str], backward: Optional[bool] = None
            ) -> Optional[float]:
    """Device ms a step in ``parts``: forward only, backward only, or both."""
    t = of_run(run)
    if t is None:
        return None
    total = 0.0
    for p in parts:
        fwd, bwd = t.parts.get(p, (0.0, 0.0))
        total += (fwd + bwd if backward is None else bwd if backward else fwd)
    return total * 1e3
