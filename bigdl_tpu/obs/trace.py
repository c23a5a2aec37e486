"""Span tracing: lightweight host-side timing seams that bridge to jax.profiler.

A :func:`span` wraps a hot-loop seam (prefetch, pad/mask, dispatch, checkpoint,
validation, summary flush) in a ``perf_counter`` timing scope on a THREAD-LOCAL
stack, and simultaneously enters a :class:`jax.profiler.TraceAnnotation` so the
same seam shows up as a named slice in device traces captured via
``Optimizer.set_profile`` / ``jax.profiler.start_trace``: on the profiler's
clock beside the device ops, where ``benchmark/lib/trace.py`` labels each idle
gap of the device with the span that covers most of it (TensorBoard's profile
plugin shows the same slices). ``docs/observability.md`` has the catalogue of
the training loop's spans: name, seam, thread, and the metric that reads each.

Recording is PULL-based and aggregate-first: span durations accumulate into a
:class:`SpanCollector` — one per :class:`~bigdl_tpu.obs.telemetry.Telemetry`
run, bound to the run's threads via :func:`bind_collector` (the driver thread
at ``run_started``; prefetch workers inherit their parent's binding). The
owning Telemetry drains its collector into each step record's ``spans``
field, so two concurrent runs with separate sinks (a fit plus a serving
Predictor) never steal each other's samples. On a thread with NO bound
collector the timing half of a span is skipped entirely — only the (cheap,
C++-side) profiler annotation remains — so a detached run pays nanoseconds
per seam, never a host sync (the BDL005 contract: spans time HOST work; they
never touch device values).

``step_annotation(n)`` wraps every jitted-step dispatch in a
``jax.profiler.StepTraceAnnotation`` so captured traces gain step boundaries.

Causal tracing rides the same seams: a :class:`TraceContext`
(``trace_id``/``span_id``/``parent_id``, deterministically derived from the
fleet identity plus a process-local counter — no wall-clock entropy in the
hot path) is bound thread-locally via :func:`bind_context` /
:func:`context_scope`. When a SAMPLED context is current, :func:`span`
additionally emits one id-bearing ``span`` telemetry record per exit through
the bound collector's ``on_span`` hook (wired by Telemetry), with the parent
chain reflecting span nesting. Head sampling is deterministic
(:func:`configure` / ``BIGDL_TRACE_SAMPLE_RATE``): rate 0 — the default —
keeps the hot path at one thread-local read per span; callers that detect a
slow request post-hoc promote it explicitly (:func:`slow_threshold_s`).
Context crosses thread seams only through the sanctioned carriers
(``spawn_worker(context=...)``, ``_DeviceBatch``/pipeline hand-off objects,
``ServeFuture.trace``) — lint BDL022 enforces this.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
import zlib
from typing import Dict, Optional

import jax

__all__ = [
    "span",
    "step_annotation",
    "add_sample",
    "SpanCollector",
    "bind_collector",
    "current_collector",
    "drain_aggregates",
    "peek_aggregates",
    "fault_point",
    "set_fault_hook",
    "fault_hook",
    "TraceContext",
    "new_context",
    "bind_context",
    "current_context",
    "context_scope",
    "configure",
    "sampling",
    "slow_threshold_s",
    "emit_span",
]

# thread-local state: .stack (nested span names), .collector (the run's sink)
_tls = threading.local()

# process-global chaos hook (resilience.chaos.FaultPlan): every span entry and
# explicit fault_point() reports its seam name here. None (the default) costs
# one module-global check; a FaultPlan installs itself only inside a chaos
# test's scope.
_fault_hook = None


def set_fault_hook(hook) -> None:
    """Install (or clear, with None) the process-global fault-injection hook —
    ``hook(seam_name)`` may raise/delay/act; see resilience.chaos."""
    global _fault_hook
    _fault_hook = hook


def fault_hook():
    return _fault_hook


def fault_point(name: str) -> None:
    """Bare chaos seam marker for paths that are not span-wrapped (a
    heartbeat write, the serving queue's admission and materialization):
    a :func:`span` reports its own name to the hook and needs no marker."""
    if _fault_hook is not None:
        _fault_hook(name)


# ---------------------------------------------------------------------------
# Causal trace context
# ---------------------------------------------------------------------------

# Deterministic id source: ids are ``<base8hex>-<seq8hex>`` where the base is
# crc32 of this process's fleet identity (host:process_index — globally unique
# across a fleet without any coordination) and seq is a process-local counter.
# No time()/random() in the allocation path: allocation order alone decides
# ids, so a seeded run produces the same ids every time.
_id_lock = threading.Lock()
_id_seq = 0
_id_base: Optional[str] = None


def _identity_base() -> str:
    global _id_base
    if _id_base is None:
        try:
            from . import fleet

            ident = fleet.process_identity()
            key = "%s:%s" % (ident.get("host"), ident.get("process_index"))
        except Exception:  # identity probe must never kill tracing
            key = "p0"
        _id_base = "%08x" % (zlib.crc32(key.encode("utf-8")) & 0xFFFFFFFF)
    return _id_base


def _reset_identity_base() -> None:
    """Test seam: forget the cached fleet-identity base (simulated fleets
    flip BIGDL_PROCESS_INDEX between runs in one process)."""
    global _id_base
    _id_base = None


def _next_seq() -> int:
    global _id_seq
    with _id_lock:
        _id_seq += 1
        return _id_seq


# Head-sampling config. sample_rate is a fraction in [0, 1]; the decision is
# deterministic (counter/key modulo the sampling period, NOT random()), so a
# fixed allocation order yields a fixed sampled subset. slow_ms is the
# promotion threshold for post-hoc emission of requests the head sample
# skipped (the batcher reconstructs those spans from the future's timestamps
# AFTER materialize, so an unsampled flight pays nothing in the hot path).
_config = {
    "sample_rate": float(os.environ.get("BIGDL_TRACE_SAMPLE_RATE", "0") or 0.0),
    "slow_ms": float(os.environ.get("BIGDL_TRACE_SLOW_MS", "250") or 250.0),
}


def configure(sample_rate: Optional[float] = None,
              slow_ms: Optional[float] = None) -> Dict[str, float]:
    """Set head-sampling knobs; returns the PREVIOUS config so tests can
    restore it (``configure(**prev)``)."""
    prev = dict(_config)
    if sample_rate is not None:
        _config["sample_rate"] = min(1.0, max(0.0, float(sample_rate)))
    if slow_ms is not None:
        _config["slow_ms"] = max(0.0, float(slow_ms))
    return prev


def sampling() -> Dict[str, float]:
    return dict(_config)


def slow_threshold_s() -> float:
    """Latency above which a request trace is always promoted (seconds)."""
    return _config["slow_ms"] / 1000.0


def _sample_decision(n: int) -> bool:
    rate = _config["sample_rate"]
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    period = max(1, int(round(1.0 / rate)))
    return (n % period) == 0


class TraceContext:
    """One node of a causal trace: ``trace_id`` names the end-to-end request
    or chunk, ``span_id`` this hop, ``parent_id`` the hop that caused it
    (None at the root). ``sampled`` is decided once at the root (head
    sampling) and inherited by every child — a trace is emitted whole or not
    at all, so no emitted span is ever orphaned from its parent chain."""

    __slots__ = ("trace_id", "span_id", "parent_id", "sampled")

    def __init__(self, trace_id: str, span_id: str,
                 parent_id: Optional[str] = None, sampled: bool = False):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.sampled = sampled

    def child(self) -> "TraceContext":
        """A new span under the same trace, parented on this one."""
        return TraceContext(
            self.trace_id,
            "%s-%08x" % (_identity_base(), _next_seq()),
            parent_id=self.span_id,
            sampled=self.sampled,
        )

    def to_fields(self) -> Dict[str, object]:
        out: Dict[str, object] = {
            "trace_id": self.trace_id, "span_id": self.span_id}
        if self.parent_id is not None:
            out["parent_id"] = self.parent_id
        return out

    def __repr__(self):
        return "TraceContext(trace=%s span=%s parent=%s sampled=%s)" % (
            self.trace_id, self.span_id, self.parent_id, self.sampled)


def new_context(key=None, sampled: Optional[bool] = None) -> TraceContext:
    """Allocate a ROOT context (a fresh trace).

    With ``key`` (any hashable/reprable value — e.g. ``(epoch, chunk_index)``
    on the input pipeline), the trace id and the sampling decision derive
    from the key's crc32, so the same logical unit of work gets the same
    trace id and the same sampling verdict on every run and for any worker
    count. Without a key both derive from the process-local counter.
    ``sampled`` overrides the head-sampling decision (slow-path promotion,
    tests)."""
    seq = _next_seq()
    base = _identity_base()
    if key is not None:
        h = zlib.crc32(repr(key).encode("utf-8")) & 0xFFFFFFFF
        trace_word, decide_n = h, h
    else:
        trace_word, decide_n = seq, seq
    if sampled is None:
        sampled = _sample_decision(decide_n)
    return TraceContext(
        trace_id="%s-%08x" % (base, trace_word & 0xFFFFFFFF),
        span_id="%s-%08x" % (base, seq),
        parent_id=None,
        sampled=bool(sampled),
    )


def bind_context(ctx: Optional[TraceContext]) -> Optional[TraceContext]:
    """Bind ``ctx`` as THIS thread's current trace context; returns the
    previous binding so callers can restore it."""
    prev = getattr(_tls, "context", None)
    _tls.context = ctx
    return prev


def current_context() -> Optional[TraceContext]:
    return getattr(_tls, "context", None)


@contextlib.contextmanager
def context_scope(ctx: Optional[TraceContext]):
    """Bind ``ctx`` for the duration of the block (exception-safe restore).
    ``None`` is allowed and simply masks any outer context."""
    prev = bind_context(ctx)
    try:
        yield ctx
    finally:
        bind_context(prev)


def emit_span(name: str, dur_s: float, ctx: TraceContext, **fields) -> None:
    """Emit one externally-timed id-bearing span record for ``ctx`` through
    THIS thread's bound collector (no-op when detached or when the collector
    has no ``on_span`` sink). The caller owns the sampling decision — this
    emits unconditionally so slow-path promotion can bypass head sampling."""
    col = getattr(_tls, "collector", None)
    sink = getattr(col, "on_span", None) if col is not None else None
    if sink is None:
        return
    rec = {"name": name, "dur_s": round(float(dur_s), 6),
           "thread": threading.current_thread().name}
    rec.update(ctx.to_fields())
    rec.update(fields)
    sink(rec)


class SpanCollector:
    """Thread-safe ``{name: (count, total_seconds)}`` table for one run.

    ``on_span`` (set by the owning Telemetry) is the id-bearing span sink:
    a callable taking one dict — the record-shaped span payload — invoked
    only for sampled contexts."""

    __slots__ = ("_lock", "_agg", "on_span")

    def __init__(self):
        self._lock = threading.Lock()
        self._agg: Dict[str, list] = {}
        self.on_span = None

    def add(self, name: str, seconds: float, count: int = 1) -> None:
        with self._lock:
            agg = self._agg.setdefault(name, [0, 0.0])
            agg[0] += count
            agg[1] += seconds

    def drain(self) -> Dict[str, Dict[str, float]]:
        """Return and CLEAR ``{name: {"n": count, "s": total_seconds}}`` —
        called by the owning Telemetry at each step emission, so spans
        recorded between two step records attribute to the later one."""
        with self._lock:
            out = {
                k: {"n": v[0], "s": round(v[1], 6)}
                for k, v in self._agg.items()
            }
            self._agg.clear()
        return out

    def peek(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                k: {"n": v[0], "s": round(v[1], 6)}
                for k, v in self._agg.items()
            }


def bind_collector(collector: Optional[SpanCollector]):
    """Bind ``collector`` as THIS thread's span sink; returns the previous
    binding so callers can restore it (``bind_collector(prev)``)."""
    prev = getattr(_tls, "collector", None)
    _tls.collector = collector
    return prev


def current_collector() -> Optional[SpanCollector]:
    return getattr(_tls, "collector", None)


def add_sample(name: str, seconds: float) -> None:
    """Record one externally-timed sample (the ``Predictor``'s dispatch seam
    times itself; the trainer's is a :func:`span`, read through ``sp.s``)."""
    col = getattr(_tls, "collector", None)
    if col is not None:
        col.add(name, seconds)


def drain_aggregates() -> Dict[str, Dict[str, float]]:
    """Drain THIS thread's bound collector ({} when unbound)."""
    col = getattr(_tls, "collector", None)
    return col.drain() if col is not None else {}


def peek_aggregates() -> Dict[str, Dict[str, float]]:
    """Non-destructive view of this thread's collector (REPL/debugging)."""
    col = getattr(_tls, "collector", None)
    return col.peek() if col is not None else {}


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class _Sample:
    """What ``with span(name) as sp`` binds: ``sp.s`` is the span's seconds
    once it has closed, so a seam that needs its own duration (the step
    record's ``dispatch_s`` and ``input_wait_s``) reads the span's one pair of
    clock reads instead of taking a second. ``None`` while the span is open,
    and on a thread with no bound collector, where a span takes no clock."""

    __slots__ = ("s",)

    def __init__(self):
        self.s = None


_UNTIMED = _Sample()  # shared by every detached span: never written


@contextlib.contextmanager
def span(name: str):
    """Time a host-side seam under ``name`` and annotate the profiler trace.

    Exception-safe (the duration is recorded even when the body raises — the
    same contract as the fixed ``Metrics.time``). Nested spans record under
    ``"outer/inner"`` paths via the thread-local stack; the profiler sees the
    bare ``name``, so bare names are unique. ``as sp`` binds a
    :class:`_Sample` whose ``s`` holds the seconds after the block.

    When a SAMPLED :class:`TraceContext` is bound on this thread and the
    collector has an ``on_span`` sink, the span also emits one id-bearing
    record on exit: a child context is bound for the body's duration so
    nested spans parent onto this one (the emitted parent chain mirrors the
    nesting stack). Emission happens even when the body raises — a fault at
    any seam closes the span rather than orphaning it.
    """
    with jax.profiler.TraceAnnotation(name):
        col = getattr(_tls, "collector", None)
        if col is None:
            if _fault_hook is not None:  # chaos seam, as below
                _fault_hook(name)
            yield _UNTIMED
            return
        ctx = getattr(_tls, "context", None)
        child = None
        if ctx is not None and ctx.sampled and col.on_span is not None:
            child = ctx.child()
            _tls.context = child
        stack = _stack()
        qualified = "/".join(stack + [name]) if stack else name
        stack.append(name)
        sample = _Sample()
        t0 = time.perf_counter()
        try:
            # chaos seam (resilience.chaos.FaultPlan), inside the clock: a
            # stall injected at a seam is time spent in that seam, and a
            # fault raised here closes the span like one raised by the body
            if _fault_hook is not None:
                _fault_hook(name)
            yield sample
        finally:
            sample.s = dt = time.perf_counter() - t0
            stack.pop()
            col.add(qualified, dt)
            if child is not None:
                _tls.context = ctx
                sink = col.on_span
                if sink is not None:
                    rec = {"name": name, "dur_s": round(dt, 6),
                           "thread": threading.current_thread().name}
                    rec.update(child.to_fields())
                    sink(rec)


def step_annotation(step_num: int):
    """``jax.profiler.StepTraceAnnotation`` around one jitted-step dispatch:
    gives profiler traces per-step boundaries (TensorBoard's step view). The
    ``dispatch`` span and its children lie inside it, and
    ``benchmark/lib/trace.label_gap`` labels an idle gap of the device with
    the span that covers most of it, the innermost of equals."""
    return jax.profiler.StepTraceAnnotation("train", step_num=int(step_num))
