"""Per-step telemetry event stream (reference: the driver-side visibility the
BigDL paper leans on — Spark accumulators like "computing time average" plus
TensorBoard summaries — unified into ONE structured stream).

A :class:`Telemetry` sink attached to any optimizer (``set_telemetry``) or
:class:`~bigdl_tpu.optim.predictor.Predictor` produces one JSON record per
step and fans it out through pluggable exporters:

* :class:`JsonlExporter` — append-only ``*.jsonl`` file (the
  ``tools/obs_report.py`` input);
* :class:`SummaryExporter` — bridges step records into an existing
  :class:`~bigdl_tpu.visualization.summary.TrainSummary` TensorBoard writer
  (same ``Loss``/``LearningRate``/``Throughput`` tags as the built-in path);
* :class:`RingBufferExporter` — bounded in-memory buffer for tests/REPL
  (every ``Telemetry`` carries one as ``.ring``).

The stream is documented in ``docs/observability.md``; ``tools/obs_report.py``
validates and summarizes it. Zero-new-host-syncs contract: every field is
derived from values the driver already holds on host (the one-step-late loss
pull, host clocks, jit-cache introspection, PJRT local memory stats) — the
stream NEVER adds a device synchronization, so the repo stays BDL005-clean
and a detached run regresses by nothing.

``Metrics`` (the host-side step-time averager that used to live in
``bigdl_tpu/optim/metrics.py``, mirroring ``$DL/optim/Metrics.scala``'s Spark
accumulators) is absorbed here; the old module remains as a thin alias.
"""

from __future__ import annotations

import collections
import contextlib
import json
import logging
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence

log = logging.getLogger("bigdl_tpu.obs")

from . import fleet as _fleet
from . import trace as _trace
from .watchdog import StallWatchdog

__all__ = [
    "Metrics",
    "Telemetry",
    "TelemetryExporter",
    "JsonlExporter",
    "RingBufferExporter",
    "SummaryExporter",
    "device_memory_stats",
]


class Metrics:
    """Host-side named averager (reference: ``$DL/optim/Metrics.scala`` —
    distributed counters via Spark accumulators, e.g. "computing time
    average", "get weights average"). Plain counters here: the mesh is driven
    by one process, so there is nothing to accumulate across executors."""

    def __init__(self):
        self._sums: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}

    def add(self, name: str, value: float) -> None:
        self._sums[name] = self._sums.get(name, 0.0) + value
        self._counts[name] = self._counts.get(name, 0) + 1

    @contextlib.contextmanager
    def time(self, name: str):
        # try/finally: an exception in the timed block (e.g. a failing step
        # inside the retry path) must still record the duration — silently
        # dropping the sample skews every average built on it
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t0)

    def average(self, name: str) -> float:
        c = self._counts.get(name, 0)
        return self._sums.get(name, 0.0) / c if c else 0.0

    def summary(self) -> Dict[str, float]:
        return {k: self.average(k) for k in sorted(self._sums)}

    def reset(self) -> None:
        self._sums.clear()
        self._counts.clear()

    def __repr__(self):
        parts = ", ".join(f"{k}: {v * 1e3:.1f}ms" for k, v in self.summary().items())
        return f"Metrics({parts})"


# --------------------------------------------------------------------------
# device memory
# --------------------------------------------------------------------------

def device_memory_stats() -> Optional[Dict[str, Dict[str, int]]]:
    """Per-device HBM stats from ``device.memory_stats()`` (PJRT local
    counters — a host-side read, never a device sync). Returns
    ``{device_label: {"bytes_in_use", "peak_bytes_in_use", ...}}`` for the
    addressable devices that report stats, or ``None`` when none do (CPU
    backends return nothing — the documented graceful fallback)."""
    import jax

    out: Dict[str, Dict[str, int]] = {}
    for d in jax.local_devices():
        getter = getattr(d, "memory_stats", None)
        if getter is None:
            continue
        try:
            stats = getter()
        except Exception:  # pragma: no cover - backend quirk, not fatal
            stats = None
        if not stats:
            continue
        out[f"{d.platform}:{d.id}"] = {
            k: int(v)
            for k, v in stats.items()
            if isinstance(v, (int, float)) and "bytes" in k
        }
    return out or None


def observe_jit_compiles(jit_fn, seen: int, telemetry: "Telemetry", *,
                         iteration: int, seconds: float, path: str,
                         cache_watch=None) -> int:
    """Report jit-cache growth across a dispatch — one cache entry per
    compiled input shape, the same executable-count introspection the
    donation tests use — as a telemetry compile event, attributing the
    dispatching call's wall ``seconds`` (trace + XLA compile; steady-state
    async dispatch is ~microseconds, so the attribution error is noise).

    ``cache_watch`` (a :class:`~bigdl_tpu.utils.compat.CacheDirWatch`)
    additionally classifies the compile against the persistent compile
    cache: ``cache_hit=True`` on the record means the executable was
    deserialized from disk (an artifact warm boot / restarted host), False
    means a fresh entry was persisted (a genuinely cold compile), absent
    means unknowable. Consulted ONLY when a compile was detected, so the
    steady-state dispatch path never pays the directory scan.

    Returns the updated seen-entry count; shared by the optimizer drivers
    and the Predictor so the two streams cannot drift. ``_cache_size`` may
    be renamed by a future jax — failure disables counting, never the run.
    """
    if jit_fn is None:
        return seen
    try:
        csize = jit_fn._cache_size()
    except Exception:
        return seen
    if csize > seen:
        cache_hit = None if cache_watch is None else cache_watch.observe()
        # the tiles this dispatch's own trace chose, what its nn.Remat
        # blocks kept and how its state-space scans were cut (it began
        # ``seconds`` ago); a program that never imported the kernel, nn.Remat
        # or the scan chose and kept none
        since = time.perf_counter() - seconds
        flash = sys.modules.get("bigdl_tpu.ops.flash_attention")
        keep = sys.modules.get("bigdl_tpu.utils.remat_keep")
        ssd = sys.modules.get("bigdl_tpu.ops.ssd")
        telemetry.compile_event(
            iteration=iteration, seconds=seconds, count=csize - seen,
            path=path, cache_hit=cache_hit,
            flash_tiles=flash and flash.take_tile_records(since=since),
            remat_kept=keep and keep.take_kept_records(since=since),
            ssd_scans=ssd and ssd.take_scan_records(since=since))
        return csize
    return seen


# --------------------------------------------------------------------------
# exporters
# --------------------------------------------------------------------------

class TelemetryExporter:
    """Exporter interface: ``emit`` one record dict; ``flush``/``close`` are
    optional. Exporters must tolerate any record ``type`` (skip what they
    don't render) so the schema can grow without breaking fan-out."""

    def emit(self, record: Dict) -> None:
        raise NotImplementedError

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


class JsonlExporter(TelemetryExporter):
    """One JSON object per line; parent dirs are created. ``append=False``
    truncates on first write — the run-dir default uses it so a re-run
    script does not stack streams in one file (a 1-compile canary summed
    over two appended runs would read as a recompile regression)."""

    def __init__(self, path: str, append: bool = True):
        self.path = path
        self.append = append
        self._fh = None

    def _file(self):
        if self._fh is None:
            d = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(d, exist_ok=True)
            self._fh = open(
                self.path, "a" if self.append else "w", encoding="utf-8"
            )
        return self._fh

    def emit(self, record: Dict) -> None:
        self._file().write(json.dumps(record, default=float) + "\n")

    def flush(self) -> None:
        if self._fh is not None:
            self._fh.flush()

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None


class RingBufferExporter(TelemetryExporter):
    """Bounded in-memory record buffer (tests/REPL)."""

    def __init__(self, capacity: int = 4096):
        self._buf: collections.deque = collections.deque(maxlen=capacity)

    def emit(self, record: Dict) -> None:
        self._buf.append(record)

    @property
    def records(self) -> List[Dict]:
        return list(self._buf)

    def steps(self) -> List[Dict]:
        return [r for r in self._buf if r.get("type") == "step"]

    def clear(self) -> None:
        self._buf.clear()


class SummaryExporter(TelemetryExporter):
    """Bridge step records into a TrainSummary-compatible TensorBoard writer
    (anything exposing ``add_scalar(tag, value, step)``), using the same tags
    the built-in ``Optimizer.set_train_summary`` path writes so dashboards
    agree regardless of which layer fed them."""

    _STEP_TAGS = (
        ("Loss", "loss"),
        ("LearningRate", "lr"),
        ("Throughput", "records_per_sec"),
    )

    def __init__(self, summary):
        self.summary = summary

    def emit(self, record: Dict) -> None:
        if record.get("type") != "step":
            return
        step = record["iteration"]
        for tag, field in self._STEP_TAGS:
            v = record.get(field)
            if v is not None:
                self.summary.add_scalar(tag, float(v), step)

    def flush(self) -> None:
        self.summary.flush()

    def close(self) -> None:
        self.summary.close()


# --------------------------------------------------------------------------
# the sink
# --------------------------------------------------------------------------

class Telemetry:
    """Unified per-step telemetry sink.

    Attach with ``optimizer.set_telemetry(Telemetry(...))`` (all four
    execution paths) or ``Predictor(model, telemetry=...)``. Every fitted
    step yields one ``type="step"`` record; compile events, stalls and run
    boundaries are interleaved as their own record types (schema:
    ``docs/observability.md``).

    Args:
        exporters: extra exporters fanned out to on every record. A
            :class:`RingBufferExporter` is always attached as ``.ring``;
            when no exporter is given and an Engine run dir resolves
            (``Engine.set_run_dir`` / ``BIGDL_RUN_DIR``), a
            :class:`JsonlExporter` at ``<run_dir>/telemetry/p<k>.jsonl``
            is added automatically — ``k`` the fleet process index
            (``obs/fleet.py``), so N processes sharing one run dir never
            collide on a single stream (the pre-fleet single-process name
            ``events.jsonl`` stays a read-compat alias in
            ``tools/obs_report.py``).
        watchdog: optional :class:`StallWatchdog`; started/stopped with the
            run, fed every step's wall time, and its stalls are emitted into
            the stream as ``type="stall"`` records.
        ring_capacity: bound of the built-in ring buffer.
        heartbeat_interval_s: floor between fleet heartbeat writes
            (``<run_dir>/fleet/p<k>.hb``, written at the step/serve emission
            seam when a run dir is configured); ``None`` disables them.
    """

    def __init__(
        self,
        exporters: Optional[Sequence[TelemetryExporter]] = None,
        watchdog: Optional[StallWatchdog] = None,
        ring_capacity: int = 4096,
        heartbeat_interval_s: Optional[float] = 1.0,
    ):
        from ..utils.engine import Engine

        # fleet identity (obs/fleet.py): stamped onto EVERY record at emit
        # so span/compile/step/serve records all carry their process tag and
        # merged multi-host reports can attribute them (docs/observability.md)
        self.identity = _fleet.process_identity()
        self.ring = RingBufferExporter(ring_capacity)
        self.exporters: List[TelemetryExporter] = [self.ring]
        if exporters:
            self.exporters.extend(exporters)
        else:
            run_dir = Engine.run_dir()
            if run_dir:
                self.exporters.append(
                    JsonlExporter(
                        os.path.join(
                            run_dir, "telemetry",
                            f"p{self.identity['process_index']}.jsonl",
                        ),
                        append=False,  # one stream per Telemetry, newest wins
                    )
                )
        # flight recorder (obs/blackbox.py): the process-global last-N rings
        # every postmortem bundle freezes. An O(1) host-side deque append per
        # record — no device sync, so BDL005/BDL008 and the 1-compile canary
        # hold with it armed. BIGDL_BLACKBOX=0 opts out.
        try:
            from . import blackbox as _blackbox

            _rec = _blackbox.ensure_armed()
            if _rec is not None:
                self.exporters.append(_rec)
        except Exception:  # lint: disable=BDL007 recorder arming is best-effort; telemetry must construct
            pass
        # fleet heartbeat throttle (perf_counter interval — BDL006) and the
        # scrape endpoint auto-attach (Engine.set_metrics_port)
        self.heartbeat_interval_s = heartbeat_interval_s
        self._hb_next = 0.0
        self._hb_disabled = False
        self._hb_last_step: Optional[int] = None
        self._hb_last_epoch: Optional[int] = None
        self._endpoint = None
        port = Engine.metrics_port()
        if port is not None:
            from . import export as _export

            # set_metrics_port already bound the endpoint; fall back to
            # starting one only if it was torn down out-of-band — and a
            # bind failure there (port re-taken meanwhile) must not abort
            # a training run over its scrape plane
            try:
                self._endpoint = (
                    _export.default_endpoint() or _export.ensure_default(port)
                )
            except OSError as e:
                log.warning(
                    "obs endpoint re-bind on port %s failed (%s); this "
                    "telemetry sink is not scrapeable", port, e,
                )
            else:
                self._endpoint.attach_telemetry(self)
        self.watchdog = watchdog
        if watchdog is not None:
            watchdog.add_callback(self._on_stall)
        self._lock = threading.RLock()
        self.compile_count = 0
        self.compile_seconds = 0.0
        self.hbm_peak_bytes: Optional[int] = None
        self._runs = 0
        # per-run span sink, bound to the run's threads (driver + prefetch
        # workers) — concurrent runs with separate sinks cannot cross-steal
        self.collector = _trace.SpanCollector()
        # id-bearing causal spans (sampled TraceContexts) emit as ``span``
        # records through this sink into the same stream as everything else
        self.collector.on_span = self.span_record
        self._prev_binding = None

    # ------------------------------------------------------------------ emit
    def emit(self, record: Dict) -> None:
        """Stamp ``ts`` (epoch timestamp — the BDL006 exemption) plus the
        fleet process identity (``process_index``/``process_count``/``host``
        — setdefault, so simulated/replayed streams keep their own tags) and
        fan out."""
        record.setdefault("ts", time.time())
        record.setdefault("process_index", self.identity["process_index"])
        record.setdefault("process_count", self.identity["process_count"])
        record.setdefault("host", self.identity["host"])
        with self._lock:
            for ex in self.exporters:
                try:
                    ex.emit(record)
                except Exception:
                    log.exception(
                        "telemetry exporter %s failed; record dropped there",
                        type(ex).__name__,
                    )

    # ------------------------------------------------------------------ span
    def span_record(self, rec: Dict) -> None:
        """Emit one id-bearing causal span as a ``type="span"`` record.

        Called from the collector's ``on_span`` hook (sampled contexts only)
        and directly by the serving layer for slow-promoted requests. ``rec``
        must carry ``name``/``trace_id``/``span_id``/``dur_s``; ``ts`` is
        stamped at emit like every record, so a span's start time is
        ``ts - dur_s``. Host-side bookkeeping only — no device values are
        read here (BDL005/BDL008)."""
        out = {"type": "span"}
        out.update(rec)
        self.emit(out)

    # ------------------------------------------------------------ run bounds
    def run_started(self, path: str, **extra) -> None:
        """Mark a run start (one per ``optimize()``/retry attempt): emits a
        ``meta`` record with topology + config context and starts the
        watchdog + span collection."""
        import jax

        from ..utils.engine import Engine

        # bind this run's span collector to the driver thread (prefetch
        # workers inherit the binding when they start)
        self._prev_binding = _trace.bind_collector(self.collector)
        self._runs += 1
        devices = [
            {"platform": d.platform, "kind": getattr(d, "device_kind", "")}
            for d in jax.local_devices()
        ]
        rec = {
            "type": "meta",
            "event": "run_start",
            "path": path,
            "devices": devices,
            "run_dir": Engine.run_dir(),
            "compile_cache_dir": Engine.compilation_cache_dir(),
            # perf surface context (docs/performance.md): whether the fused
            # Pallas kernel paths were on for this run and which XLA
            # scheduler/combiner flags Engine manages — a bench/report reader
            # can tell two runs' configurations apart from the stream alone
            "fused_kernels": Engine.fused_kernels(),
            "xla_flags": Engine.xla_flags() or None,
            # knobs requested but left to the user's own XLA_FLAGS pin
            "xla_flags_env_pinned": list(Engine.xla_flags_env_pinned()) or None,
        }
        rec.update(extra)
        self.emit(rec)
        self.flush()  # run boundaries hit disk immediately (tail -f works)
        self._hb_next = 0.0  # run start heartbeats immediately
        self._heartbeat(rec)
        if self.watchdog is not None:
            self.watchdog.start()

    def run_ended(self, path: str, **extra) -> None:
        rec = {
            "type": "meta",
            "event": "run_end",
            "path": path,
            "compile_count": self.compile_count,
            "compile_seconds": round(self.compile_seconds, 6),
            "hbm_peak_bytes": self.hbm_peak_bytes,
            # drain tail spans (the final flush / end-of-run checkpoint land
            # AFTER the last step record) so they attribute to THIS run
            # instead of leaking into the next run's first step
            "spans": self.collector.drain(),
        }
        rec.update(extra)
        self.emit(rec)
        if self.watchdog is not None:
            self.watchdog.stop()
        # restore the binding only where THIS run holds it: run_ended may
        # execute on a different thread than run_started (e.g. a
        # ModelServer closed from a shutdown thread), and blindly rebinding
        # there would clobber that thread's own collector while the
        # starting thread's binding can only be cleaned by its own later
        # run anyway
        if _trace.current_collector() is self.collector:
            _trace.bind_collector(self._prev_binding)
        self._prev_binding = None
        self._hb_next = 0.0  # final heartbeat carries the run-end state
        self._heartbeat(rec)
        self.flush()

    # ------------------------------------------------------------------ step
    def step(
        self,
        *,
        iteration: int,
        records: int,
        wall_s: float,
        path: str = "train",
        epoch: Optional[int] = None,
        loss: Optional[float] = None,
        lr: Optional[float] = None,
        records_per_sec: Optional[float] = None,
        dispatch_s: Optional[float] = None,
        input_wait_s: Optional[float] = None,
        input_qdepth: Optional[int] = None,
        **extra,
    ) -> Dict:
        """Emit one per-step record. All inputs are host-side values the
        caller already holds (zero new device syncs by construction).
        ``input_wait_s``/``input_qdepth`` are the host input-pipeline
        starvation gauges: the prefetch worker's wait for this step's batch
        and the pipeline staging-ring depth right after the pull
        (``tools/obs_report.py`` derives ``input_starved_pct`` from them)."""
        mem = device_memory_stats()
        if mem:
            peak = max(
                s.get("peak_bytes_in_use", s.get("bytes_in_use", 0))
                for s in mem.values()
            )
            with self._lock:
                self.hbm_peak_bytes = max(self.hbm_peak_bytes or 0, peak)
        rec = {
            "type": "step",
            "path": path,
            "iteration": int(iteration),
            "epoch": None if epoch is None else int(epoch),
            "loss": loss,
            "lr": lr,
            "records": int(records),
            "wall_s": round(float(wall_s), 6),
            "records_per_sec": (
                None if records_per_sec is None else round(records_per_sec, 3)
            ),
            "dispatch_s": (
                None if dispatch_s is None else round(dispatch_s, 6)
            ),
            "input_wait_s": (
                None if input_wait_s is None else round(float(input_wait_s), 6)
            ),
            "input_qdepth": (
                None if input_qdepth is None else int(input_qdepth)
            ),
            "compile_count": self.compile_count,
            "compile_s": round(self.compile_seconds, 6),
            "spans": self.collector.drain(),
            "memory": mem,
            "hbm_peak_bytes": self.hbm_peak_bytes,
        }
        rec.update(extra)
        self.emit(rec)
        self._heartbeat(rec)
        if self.watchdog is not None:
            self.watchdog.notify_step(wall_s)
        return rec

    # ----------------------------------------------------------------- serve
    def serve(
        self,
        *,
        model: str,
        iteration: int,
        records: int,
        batch_fill: float,
        queue_depth: int,
        path: str = "serve",
        bucket: Optional[int] = None,
        version: Optional[int] = None,
        trigger: Optional[str] = None,
        wall_s: Optional[float] = None,
        queue_wait_ms: Optional[float] = None,
        p50_ms: Optional[float] = None,
        p99_ms: Optional[float] = None,
        rps: Optional[float] = None,
        deadline_missed: Optional[int] = None,
        swept_expired: Optional[int] = None,
        shed: Optional[int] = None,
        breaker_state: Optional[str] = None,
        **fields,
    ) -> None:
        """One serving-runtime record per continuous-batcher flush
        (``bigdl_tpu/serving``): which model/version dispatched, how full the
        batch was (``batch_fill`` = real records / max_batch), the queue depth
        left behind, which SLO trigger fired (``"max_batch"`` /
        ``"max_delay"`` / ``"drain"``), and the rolling end-to-end latency
        percentiles + requests/sec over completed (caller-materialized)
        requests. Host-side values only — the batching thread never
        materializes device results (lint rule BDL010); buffered like step
        records (flush happens at run boundaries / ``ModelServer.close``).

        Resilience gauges (docs/observability.md): ``deadline_missed`` /
        ``swept_expired`` are CUMULATIVE expired-request counters (all
        misses / the sweep-seam subset), ``shed`` the cumulative submits
        refused by an open circuit breaker, ``breaker_state`` the breaker's
        state at flush time — the open/close transitions themselves land as
        immediate ``warn reason=circuit_open/circuit_closed`` records."""
        rec = {
            "type": "serve",
            "path": path,
            "model": model,
            "iteration": int(iteration),
            "records": int(records),
            "batch_fill": batch_fill,
            "queue_depth": int(queue_depth),
            "bucket": None if bucket is None else int(bucket),
            "version": None if version is None else int(version),
            "trigger": trigger,
            "wall_s": None if wall_s is None else round(wall_s, 6),
            "queue_wait_ms": (
                None if queue_wait_ms is None else round(queue_wait_ms, 3)
            ),
            "p50_ms": None if p50_ms is None else round(p50_ms, 3),
            "p99_ms": None if p99_ms is None else round(p99_ms, 3),
            "rps": None if rps is None else round(rps, 3),
        }
        for key, val in (
            ("deadline_missed", deadline_missed),
            ("swept_expired", swept_expired),
            ("shed", shed),
        ):
            if val is not None:
                rec[key] = int(val)
        if breaker_state is not None:
            rec["breaker_state"] = breaker_state
        rec.update(fields)
        self.emit(rec)
        self._heartbeat(rec)

    # ------------------------------------------------------------------ perf
    def perf(self, *, iteration: int, window: int, breakdown: Dict,
             path: str = "train", epoch: Optional[int] = None,
             **fields) -> None:
        """One performance-accounting record every N steps (obs/perf.py):
        the windowed compute/comms/input/host step-time decomposition plus
        the cost-model join — ``model_flops`` / ``achieved_flops_s`` /
        ``mfu`` / ``arithmetic_intensity`` / roofline ``bound`` — all
        derived from host clocks and one-per-compile program metadata, so
        the record costs no device sync (schema: docs/observability.md).
        Buffered like step records (the stride bounds its rate)."""
        rec = {
            "type": "perf",
            "path": path,
            "iteration": int(iteration),
            "epoch": None if epoch is None else int(epoch),
            "window": int(window),
            "breakdown": breakdown,
        }
        rec.update(fields)
        self.emit(rec)

    # ---------------------------------------------------------------- health
    def health(self, *, iteration: int, path: str = "train",
               epoch: Optional[int] = None, **fields) -> None:
        """One model-health record (obs/health.py): per-layer gradient/weight
        norms, update/weight ratios, non-finite counters, and (when hooks are
        installed) activation statistics — all computed IN-GRAPH by the train
        step and pulled at the one-step-late seam, so the record costs no new
        device sync. Buffered like step records (the stride already bounds
        its rate)."""
        rec = {
            "type": "health",
            "path": path,
            "iteration": int(iteration),
            "epoch": None if epoch is None else int(epoch),
        }
        rec.update(fields)
        self.emit(rec)

    # ------------------------------------------------------------------ warn
    def warn(self, *, reason: str, path: str = "train",
             iteration: Optional[int] = None, **fields) -> None:
        """One advisory ``warn`` record — a condition worth an operator's
        attention that needs no recovery action (e.g. the ``update_ratio``
        auto-LR guard tripping before the divergence guard would). Flushes
        immediately: warnings exist to be seen while the run is still
        correctable."""
        rec = {
            "type": "warn",
            "path": path,
            "reason": reason,
            "iteration": None if iteration is None else int(iteration),
        }
        rec.update(fields)
        self.emit(rec)
        self.flush()

    # --------------------------------------------------------------- compile
    def compile_event(
        self, *, iteration: int, seconds: float, count: int = 1,
        path: str = "train", cache_hit: Optional[bool] = None,
        flash_tiles: Optional[List[Dict]] = None,
        remat_kept: Optional[List[Dict]] = None,
        ssd_scans: Optional[List[Dict]] = None,
    ) -> None:
        """One (re)compilation observed — hooked off the jit-cache-size delta
        at dispatch, the same introspection PR 2's ``compile_seconds``
        plumbing exposed. ``seconds`` is the dispatch wall of the compiling
        call (trace + XLA compile + first execution enqueue). ``cache_hit``
        (tri-state) says whether the persistent compile cache served the
        executable from disk — True on every compile is the artifact warm
        boot's telemetry proof of "0 fresh compiles". ``flash_tiles`` lists
        the flash-attention tile choices that the compiling call's trace
        made (``ops/flash_attention.take_tile_records``), ``remat_kept`` the
        marked values that its ``nn.Remat`` blocks kept for the backward
        (``utils/remat_keep.take_kept_records``), ``ssd_scans`` how its
        state-space scans were cut (chunk, chunks a record, head group:
        ``ops/ssd.take_scan_records``); the record carries each field only
        where there were any."""
        with self._lock:
            self.compile_count += count
            self.compile_seconds += seconds
        record = {
            "type": "compile",
            "path": path,
            "iteration": int(iteration),
            "count": int(count),
            "seconds": round(seconds, 6),
            "total_compiles": self.compile_count,
            "cache_hit": cache_hit,
        }
        if flash_tiles:
            record["flash_tiles"] = flash_tiles
        if remat_kept:
            record["remat_kept"] = remat_kept
        if ssd_scans:
            record["ssd_scans"] = ssd_scans
        self.emit(record)
        self.flush()  # compiles are rare; make them tail-able immediately

    # ---------------------------------------------------------------- warmup
    def warmup(self, *, model: str, seconds: float, compiles: int,
               fresh_compiles: Optional[int], warm_start: bool,
               path: str = "serve", **fields) -> None:
        """One record per model warmup (``ModelServer`` registration or
        artifact warm boot): how long the bucket replay took, how many
        executables it traced (``compiles``), and — the cold-start headline —
        how many wrote FRESH persistent-cache entries (``fresh_compiles``;
        0 on a warm boot means every bucket was a disk read, None when no
        cache dir is configured so freshness is unknowable). ``warm_start``
        marks boots driven from an artifact bundle. Flushes immediately:
        boot telemetry exists to be read while the fleet is scaling."""
        rec = {
            "type": "warmup",
            "path": path,
            "model": model,
            "seconds": round(float(seconds), 6),
            "compiles": int(compiles),
            "fresh_compiles": (
                None if fresh_compiles is None else int(fresh_compiles)
            ),
            "warm_start": bool(warm_start),
        }
        rec.update(fields)
        self.emit(rec)
        self.flush()

    # ------------------------------------------------------------ resilience
    # The resilience runtime's record types (docs/resilience.md): every one
    # flushes immediately — they mark the exact moments an operator tailing
    # events.jsonl needs to see (a retry in progress, a rollback, a
    # preemption about to exit the process).

    def retry_event(self, *, attempt: int, fault_class: str,
                    backoff_s: float = 0.0, path: str = "train",
                    error: Optional[str] = None, action: str = "resume",
                    skip_position=None) -> None:
        """One failure the FailurePolicy decided to retry: classification,
        cumulative attempt count, chosen backoff, and the data position being
        poisoned-and-skipped (if any)."""
        self.emit(
            {
                "type": "retry",
                "path": path,
                "attempt": int(attempt),
                "fault_class": fault_class,
                "backoff_s": round(float(backoff_s), 6),
                "error": error,
                "action": action,
                "skip_position": skip_position,
            }
        )
        self.flush()

    def rollback_event(self, *, reason: str, restored_step: Optional[int],
                       iteration: Optional[int] = None,
                       lr_scale: Optional[float] = None,
                       path: str = "train",
                       layer: Optional[str] = None,
                       source: Optional[str] = None,
                       shard: Optional[str] = None) -> None:
        """The divergence guard rolled the run back: why, to which verified
        checkpoint step (None = the step-0 entry snapshot), and the LR
        backoff scale now in force. With a HealthMonitor attached, ``layer``
        names the first non-finite parameter path of the diverged step and
        ``source`` whether grads or weights poisoned it ("loss" = every
        parameter counter clean); both None without ``set_health``."""
        self.emit(
            {
                "type": "rollback",
                "path": path,
                "reason": reason,
                "restored_step": (
                    None if restored_step is None else int(restored_step)
                ),
                "iteration": None if iteration is None else int(iteration),
                "lr_scale": None if lr_scale is None else float(lr_scale),
                "layer": layer,
                "source": source,
                # GSPMD/hybrid mesh-shard localization (None elsewhere):
                # which data-axis shard's rows carried the non-finite values
                "shard": shard,
            }
        )
        self.flush()

    def preempt_event(self, *, signal: int, step: int, path: str = "train",
                      checkpoint_dir: Optional[str] = None) -> None:
        """A preemption signal was handled: the emergency checkpoint (if a
        path was configured) is on disk when this record lands."""
        self.emit(
            {
                "type": "preempt_checkpoint",
                "path": path,
                "signal": int(signal),
                "step": int(step),
                "checkpoint_dir": checkpoint_dir,
            }
        )
        self.flush()

    def fault_injected_event(self, *, seam: str, kind: str, hit: int) -> None:
        """A chaos FaultPlan fired at an armed seam (resilience.chaos) —
        makes chaos runs self-describing in the stream."""
        self.emit(
            {
                "type": "fault_injected",
                "seam": seam,
                "kind": kind,
                "hit": int(hit),
            }
        )
        self.flush()

    # ------------------------------------------------------------- heartbeat
    def _heartbeat(self, rec: Dict) -> None:
        """Fleet heartbeat at the emission seam (``obs/fleet.py``): an
        atomic JSON touch of ``<run_dir>/fleet/p<k>.hb`` carrying the latest
        step/record summary, throttled to ``heartbeat_interval_s`` so the
        hot path pays at most one small file rename per interval. Host-side
        state only (the record dict the caller just built) — zero device
        syncs, like everything else in this module. A write failure
        disables heartbeats for this sink with one warning; it never fails
        the run."""
        if self._hb_disabled or self.heartbeat_interval_s is None:
            return
        now = time.perf_counter()
        if now < self._hb_next:
            return
        from ..utils.engine import Engine

        run_dir = Engine.run_dir()
        if not run_dir:
            return
        self._hb_next = now + self.heartbeat_interval_s
        # meta/warn records carry no iteration: fall back to the last seen
        # step so a run-end heartbeat still reports how far this process got
        step = rec.get("iteration")
        if step is None:
            step = self._hb_last_step
        else:
            self._hb_last_step = step
        epoch = rec.get("epoch")
        if epoch is None:
            epoch = self._hb_last_epoch
        else:
            self._hb_last_epoch = epoch
        summary = {"type": rec.get("type")}
        for key in ("loss", "records_per_sec", "path", "model",
                    "queue_depth", "event"):
            if rec.get(key) is not None:
                summary[key] = rec[key]
        try:
            _fleet.write_heartbeat(
                run_dir,
                identity=self.identity,
                step=step,
                epoch=epoch,
                wall_s=rec.get("wall_s"),
                summary=summary,
            )
        except OSError:
            self._hb_disabled = True
            log.warning(
                "fleet heartbeat write under %s failed; heartbeats disabled "
                "for this telemetry sink", run_dir, exc_info=True,
            )

    # ----------------------------------------------------------------- stall
    def _on_stall(self, info: Dict) -> None:
        rec = {"type": "stall"}
        rec.update(info)
        self.emit(rec)
        # flush NOW: the stall record exists precisely because the run is
        # wedged — run_ended (the usual flush point) may never execute, and
        # an operator tailing events.jsonl must see the stall immediately
        self.flush()
        # a declared stall IS an abnormal exit in waiting: freeze the rings
        # while the wedged thread's stack is still the interesting one
        try:
            from . import blackbox as _blackbox

            _blackbox.dump_postmortem(
                "stall_declared", telemetry=self, extra={"stall": info})
        except Exception:  # lint: disable=BDL007 the stall is already declared; a dump fault must not mask it
            pass

    # ----------------------------------------------------------- maintenance
    def flush(self) -> None:
        with self._lock:
            for ex in self.exporters:
                try:
                    ex.flush()
                except Exception:
                    log.exception("telemetry exporter flush failed")

    def close(self) -> None:
        if self._endpoint is not None:
            self._endpoint.detach_telemetry(self)
            self._endpoint = None
        if self.watchdog is not None:
            self.watchdog.stop()
        # clean-shutdown sentinel (docs/resilience.md "Elastic fleet"): one
        # final heartbeat with leaving=True, unthrottled, so the
        # FleetMonitor classifies this process as host_left — a graceful
        # exit must never trigger emergency resharding. Best-effort, like
        # every heartbeat write.
        if not self._hb_disabled and self.heartbeat_interval_s is not None:
            from ..utils.engine import Engine

            run_dir = Engine.run_dir()
            if run_dir:
                try:
                    _fleet.write_heartbeat(
                        run_dir,
                        identity=self.identity,
                        step=self._hb_last_step,
                        epoch=self._hb_last_epoch,
                        leaving=True,
                    )
                except OSError:
                    log.warning(
                        "leaving-sentinel heartbeat under %s failed",
                        run_dir, exc_info=True,
                    )
        with self._lock:
            for ex in self.exporters:
                try:
                    ex.close()
                except Exception:
                    log.exception("telemetry exporter close failed")
