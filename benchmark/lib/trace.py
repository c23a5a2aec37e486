"""From a profiler trace to numbers: one ``Event`` shape, a reader for the
chrome-trace JSON (``chrome_trace``) beside the one for xplane (``xplane``),
and ONE reduction over the events.

Times are integer picoseconds on the trace's clock. A device is a plane named
``/device:...``; its ops are on the line ``XLA Ops`` and each execution of a
jitted program is one event on the line ``XLA Modules``. Everything else is
the host: ``jax.profiler.TraceAnnotation`` spans of the program's own threads.
"""

from __future__ import annotations

import collections
import gzip
import json
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PS = 1e-12


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    category: Optional[str]  # hlo_category of a device op, else None
    start_ps: int
    dur_ps: int


def read_chrome_trace(path: str) -> List[Event]:
    """The events of a ``*.trace.json(.gz)`` (timestamps in microseconds)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        raw = json.load(f)["traceEvents"]
    planes, lines = {}, {}
    for e in raw:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            planes[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            lines[(e["pid"], e["tid"])] = e["args"]["name"]
    out = []
    for e in raw:
        if e.get("ph") != "X" or e.get("name", "").startswith("$"):
            continue
        out.append(Event(
            planes.get(e["pid"], str(e["pid"])),
            lines.get((e["pid"], e.get("tid")), str(e.get("tid"))),
            e["name"],
            e.get("args", {}).get("hlo_category"),
            round(e["ts"] * 1e6),
            round(e.get("dur", 0) * 1e6),
        ))
    return out


def is_device(plane: str) -> bool:
    return plane.startswith("/device:")


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted intervals: two overlapping ops are busy time once."""
    out: List[List[int]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: int, b: int, lo: int, hi: int) -> Tuple[int, int]:
    return max(a, lo), min(b, hi)


class Window(NamedTuple):
    start_ps: int
    end_ps: int
    steps: int


def whole_window(device_events: Sequence[Event]) -> Window:
    """First op's start to last op's end; a step is one program execution."""
    ops = [e for e in device_events if e.line == OPS_LINE]
    mods = [e for e in device_events if e.line == MODULES_LINE]
    return Window(min(e.start_ps for e in ops),
                  max(e.start_ps + e.dur_ps for e in ops), max(len(mods), 1))


def steady_window(device_events: Sequence[Event]) -> Optional[Window]:
    """Whole periods of the program that took most of the device's time (the
    train step, not the small programs beside it): from the start of its
    second execution in the trace to the start of its last, so a period holds
    the step and the gap after it, and an execution the trace caught only
    part of (the first, the last) is left out."""
    total = collections.Counter()
    for e in device_events:
        if e.line == MODULES_LINE:
            total[e.name] += e.dur_ps
    if not total:
        return None
    top = total.most_common(1)[0][0]
    starts = sorted(e.start_ps for e in device_events
                    if e.line == MODULES_LINE and e.name == top)
    if len(starts) < 3:
        return None
    return Window(starts[1], starts[-1], len(starts) - 2)


class Reduced(NamedTuple):
    chips: int
    steps: int
    window_s: float            # mean over the chips
    busy_s: float              # union of op intervals, mean over the chips
    category_s: Dict[str, float]   # summed op time by hlo_category, mean
    idle_gaps: List[Tuple[str, float]]  # longest first, on the first chip


def label_gap(gap: Tuple[int, int], host: Sequence[Event]) -> str:
    """The host span that covers most of the gap; of equals, the innermost."""
    best, best_key = "unattributed", (0, 0)
    for e in host:
        a, b = _clip(e.start_ps, e.start_ps + e.dur_ps, *gap)
        if b > a and (b - a, -e.dur_ps) > best_key:
            best, best_key = e.name, (b - a, -e.dur_ps)
    return best


def reduce_events(events: Sequence[Event], steady: bool = True,
                  n_gaps: int = 5) -> Reduced:
    """Busy time, time by category and the longest idle gaps, per device and
    then averaged. Raises when no device op is in the trace."""
    by_plane: Dict[str, List[Event]] = collections.defaultdict(list)
    host = []
    for e in events:
        (by_plane[e.plane] if is_device(e.plane) else host).append(e)
    by_plane = {p: ev for p, ev in by_plane.items()
                if any(e.line == OPS_LINE for e in ev)}
    if not by_plane:
        raise ValueError("trace holds no device op (no '/device:' plane with "
                         f"a line {OPS_LINE!r})")
    windows, busys, cats, gaps, steps = [], [], collections.Counter(), [], []
    for i, plane in enumerate(sorted(by_plane)):
        ev = by_plane[plane]
        win = (steady_window(ev) if steady else None) or whole_window(ev)
        ops = [e for e in ev if e.line == OPS_LINE
               and win.start_ps <= e.start_ps < win.end_ps]
        merged = union(_clip(e.start_ps, e.start_ps + e.dur_ps,
                             win.start_ps, win.end_ps) for e in ops)
        windows.append((win.end_ps - win.start_ps) * PS)
        busys.append(sum(b - a for a, b in merged) * PS)
        steps.append(win.steps)
        for e in ops:
            cats[e.category or "uncategorized"] += e.dur_ps * PS
        if i == 0:
            edges = [win.start_ps] + [t for ab in merged for t in ab] + [win.end_ps]
            idle = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k]]
            idle.sort(key=lambda g: g[0] - g[1])
            gaps = [(label_gap(g, host), (g[1] - g[0]) * PS)
                    for g in idle[:n_gaps]]
    n = len(by_plane)
    return Reduced(n, min(steps), sum(windows) / n, sum(busys) / n,
                   {c: s / n for c, s in cats.items()}, gaps)
