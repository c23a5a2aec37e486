"""Performance observability: always-on MFU/roofline accounting, step-time
decomposition, and anomaly-triggered profiler capture.

Where :mod:`~bigdl_tpu.obs.health` answers "why is the model unhealthy" and
:mod:`~bigdl_tpu.obs.fleet` answers "which host is behind", this module
answers "**how fast is the hardware actually running, and why not faster**"
— continuously, on every telemetry-attached run:

* **Cost model** — :func:`program_cost` derives a step's model FLOPs / HBM
  bytes / collective operand bytes ONCE per compiled program from the
  sanctioned introspection seam (:mod:`~bigdl_tpu.obs.profiler` — HLO cost
  analysis + StableHLO collective parsing; lint rule BDL016 keeps every
  other module away from the lowering internals). Nothing here ever reads a
  device value: the cost is program metadata, the wall times are the host
  clocks the driver already holds, so the BDL005/BDL008 zero-new-host-syncs
  contract is preserved by construction.
* **Accounting** — :class:`PerfAccountant` joins that per-program cost with
  each step's wall at the existing one-step-late flush seam: every ``step``
  record gains ``model_flops`` / ``achieved_flops_s`` / ``mfu`` (``None``-
  graceful where the backend has no peak entry — CPU), and every
  ``every_n_steps`` steps a ``type="perf"`` record lands with the windowed
  **compute / comms / input / host** step-time decomposition and the
  roofline classification (compute- vs bandwidth-bound, from arithmetic
  intensity against the device ridge point).
* **Monitoring** — :class:`PerfMonitor` (on the
  :class:`~bigdl_tpu.obs.watchdog.MonitorBase` chassis, directly drivable
  with no thread and no sleeps) watches the rolling step-time median and the
  MFU trend against a frozen early-run baseline; a breach emits ONE
  ``warn reason=perf_regression`` per episode — naming the degraded
  component from the decomposition — and triggers ONE bounded
  ``jax.profiler`` trace window into ``<run_dir>/profile/`` (re-arming on
  recovery, so a relapse captures again). The chaos ``delay`` seam drives
  the whole path on CPU.
* **Capture seam** — :func:`start_capture` / :func:`stop_capture` are the
  ONLY sanctioned ``jax.profiler`` capture calls outside this module and
  ``obs/profiler.py`` (lint rule BDL016): they serialize concurrent capture
  requests (``Optimizer.set_profile`` windows and monitor-triggered
  captures share one profiler) so two windows can never interleave.

Peak hardware numbers come from :func:`bigdl_tpu.utils.compat.device_peaks`.
These figures are live telemetry, not the yardstick: the measured speeds are
``benchmark/``'s (``PERF.md`` §2-§3), and ``bytes_accessed`` over-counts HBM
traffic on the TPU (``PERF.md`` §6, PR 24).
Schema + knobs: docs/observability.md; the walkthrough: docs/performance.md.
"""

from __future__ import annotations

import collections
import logging
import os
import statistics
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

from .watchdog import MonitorBase

log = logging.getLogger("bigdl_tpu.obs")

__all__ = [
    "PerfConfig",
    "PerfAccountant",
    "PerfMonitor",
    "StepCost",
    "pipeline_bubble_fraction",
    "program_cost",
    "predictor_bucket_costs",
    "achieved_flops_s",
    "mfu",
    "classify_roofline",
    "start_capture",
    "stop_capture",
    "capture_active",
]

# breakdown component keys, in render order (the ``perf`` record's
# ``breakdown`` object and the PerfMonitor's component attribution share them)
COMPONENTS = ("compute_s", "comms_s", "input_s", "host_s")


# --------------------------------------------------------------------------
# the sanctioned jax.profiler capture seam (lint rule BDL016)
# --------------------------------------------------------------------------

_capture_lock = threading.Lock()
_capture_dir: Optional[str] = None


def start_capture(trace_dir: str) -> bool:
    """Start ONE ``jax.profiler`` trace into ``trace_dir``; returns False
    when a capture is already running (there is one profiler per process —
    a second ``start_trace`` would abort it, so concurrent requests from a
    ``set_profile`` window and a PerfMonitor breach must serialize here).
    A profiler-side failure (no TB profile plugin deps, a stale session)
    degrades to False with a log line, never an exception in the driver."""
    global _capture_dir
    import jax

    with _capture_lock:
        if _capture_dir is not None:
            return False
        try:
            jax.profiler.start_trace(trace_dir)
        except Exception as e:  # capture is advisory; the run must not die
            log.warning("profiler capture into %s failed to start: %s",
                        trace_dir, e)
            return False
        _capture_dir = trace_dir
        return True


def stop_capture() -> Optional[str]:
    """Stop the active capture (no-op when none is running); returns the
    trace dir that was being written, or None."""
    global _capture_dir
    import jax

    with _capture_lock:
        d, _capture_dir = _capture_dir, None
        if d is None:
            return None
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # already stopped / profiler died: not fatal
            log.warning("profiler capture stop raised: %s", e)
        return d


def capture_active() -> bool:
    with _capture_lock:
        return _capture_dir is not None


# --------------------------------------------------------------------------
# cost model
# --------------------------------------------------------------------------

@dataclass
class StepCost:
    """One compiled program's cost-model figures (host metadata only).

    ``flops`` / ``bytes_accessed`` come from the HLO cost analysis
    (``obs/profiler.py``'s sanctioned seam); ``collective_bytes`` /
    ``grad_exchange_bytes`` from the StableHLO collective-operand parser
    (PR 12's compressed-comms lock). All fields ``None``-graceful: a backend
    without a cost model yields an empty cost, and every consumer degrades.
    """

    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    arithmetic_intensity: Optional[float] = None
    collective_bytes: Optional[int] = None
    grad_exchange_bytes: Optional[int] = None
    # pp/ep comms classification (PR 17): the pipeline ring-shift bytes
    # (ppermute → collective_permute) and expert-dispatch bytes (the MoE
    # all_to_all hops), broken out of ``collective_bytes`` so the perf
    # records name which parallelism paid the wire time
    all_to_all_bytes: Optional[int] = None
    ppermute_bytes: Optional[int] = None

    def fields(self) -> Dict:
        return {
            "model_flops": self.flops,
            "hbm_bytes_accessed": self.bytes_accessed,
            "arithmetic_intensity": self.arithmetic_intensity,
            "collective_bytes": self.collective_bytes,
        }


def program_cost(fn, specs) -> Optional[StepCost]:
    """Derive a jitted function's :class:`StepCost` from abstract input specs
    (``ShapeDtypeStruct`` trees — metadata only, safe on donated buffers).

    One lowering per call — run it ONCE per compile, off the hot path (the
    PerfAccountant does it at the first one-step-late flush, while the
    device is busy with the next dispatched step). All introspection goes
    through :mod:`~bigdl_tpu.obs.profiler` (the sanctioned seam): HLO cost
    analysis for flops/bytes, StableHLO text for collective operand bytes.
    Returns None when the program cannot be lowered or reports no cost."""
    from . import profiler

    try:
        lowered = fn.lower(*specs)
    except Exception as e:  # exotic step signature: accounting degrades
        log.warning("perf cost model: lowering failed (%s); "
                    "MFU accounting disabled for this step", e)
        return None
    coll = None
    try:
        coll = profiler.collective_bytes(lowered)
    except Exception:  # pure-text parse; a new op spelling must not kill it
        log.debug("perf cost model: collective parse failed", exc_info=True)
    cost = profiler.lowered_cost_summary(lowered)
    if cost is None and not (coll and coll.get("total_bytes")):
        return None
    cost = cost or {}
    return StepCost(
        flops=cost.get("flops"),
        bytes_accessed=cost.get("bytes_accessed"),
        arithmetic_intensity=cost.get("arithmetic_intensity"),
        collective_bytes=(coll or {}).get("total_bytes"),
        grad_exchange_bytes=(coll or {}).get("grad_exchange_bytes"),
        all_to_all_bytes=(coll or {}).get("all_to_all_bytes"),
        ppermute_bytes=(coll or {}).get("ppermute_bytes"),
    )


def pipeline_bubble_fraction(n_stages: int, n_micro: int) -> float:
    """The GPipe schedule's idle fraction: T = n_micro + S - 1 ticks, of
    which S - 1 are ramp-up/drain bubbles per stage — (S-1)/(n_micro+S-1).
    The :class:`PerfAccountant` stamps it on every step as
    ``pipe_bubble_frac``."""
    if n_stages < 1 or n_micro < 1:
        raise ValueError(
            f"need n_stages >= 1 and n_micro >= 1, got {n_stages}/{n_micro}"
        )
    return (n_stages - 1) / (n_micro + n_stages - 1)


def achieved_flops_s(flops: Optional[float],
                     wall_s: Optional[float]) -> Optional[float]:
    if not flops or not wall_s or wall_s <= 0:
        return None
    return flops / wall_s


def mfu(flops: Optional[float], wall_s: Optional[float],
        peak_flops: Optional[float], n_devices: int = 1) -> Optional[float]:
    """Model FLOPs utilization: achieved model flops/s over the peak of the
    participating chips. None wherever a term is unknown (CPU backends have
    no peak entry — the documented graceful fallback)."""
    ach = achieved_flops_s(flops, wall_s)
    if ach is None or not peak_flops or n_devices < 1:
        return None
    return round(ach / (peak_flops * n_devices), 6)


def classify_roofline(arithmetic_intensity: Optional[float],
                      peak_flops: Optional[float],
                      hbm_bytes_s: Optional[float]) -> Optional[str]:
    """Roofline classification of a program: ``"compute"``-bound when its
    arithmetic intensity (flops per HBM byte) exceeds the device ridge point
    ``peak_flops / hbm_bytes_s``, else ``"bandwidth"``-bound. None when any
    term is unknown."""
    if not arithmetic_intensity or not peak_flops or not hbm_bytes_s:
        return None
    ridge = peak_flops / hbm_bytes_s
    return "compute" if arithmetic_intensity >= ridge else "bandwidth"


def predictor_bucket_costs(predictor, sample, shape_buckets=None) -> Dict:
    """Per-bucket serving cost table for a warmed :class:`Predictor`:
    ``{bucket_key: {"flops", "flops_per_record", "peak_flops_total"}}``
    where ``bucket_key`` is the shape bucket (or None for the fixed-shape
    path). Derived ONCE at ``ModelServer`` warmup — never on the batching
    thread (BDL010) — so each serve record can carry its flush's
    achieved-throughput-vs-bucket-cost figures as plain arithmetic.
    Returns {} when the model reports no cost."""
    import jax

    from ..utils.compat import device_peaks

    def spec(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree
        )

    params = spec(predictor.model.get_parameters())
    state = spec(predictor.model.get_state())
    peaks = device_peaks()
    peak_total = (
        peaks.flops * predictor._n_dev
        if peaks is not None and peaks.flops else None
    )
    shapes: Dict = {}
    if shape_buckets:
        for b in shape_buckets:
            shapes[int(b)] = (predictor.batch_size, int(b)) + tuple(
                sample.shape[1:]
            )
    else:
        shapes[None] = (predictor.batch_size,) + tuple(sample.shape)
    out: Dict = {}
    for key, shp in shapes.items():
        x_spec = jax.ShapeDtypeStruct(shp, sample.dtype)
        cost = program_cost(predictor._compiled(), (params, state, x_spec))
        if cost is None or not cost.flops:
            continue
        out[key] = {
            "flops": cost.flops,
            "flops_per_record": cost.flops / predictor.batch_size,
            "peak_flops_total": peak_total,
        }
    return out


# --------------------------------------------------------------------------
# configuration
# --------------------------------------------------------------------------

@dataclass
class PerfConfig:
    """Knobs for the always-on perf surface (docs/observability.md).

    Args:
        every_n_steps: ``perf`` record stride (the decomposition window).
        cost: derive the program cost model (one extra lowering per compile,
            off the hot path). ``False`` keeps the decomposition/monitor but
            drops flops/MFU. Also killable per process via
            ``BIGDL_PERF_COST=0``.
        peak_flops: per-chip peak override (flops/s). ``None`` resolves the
            backend through :func:`~bigdl_tpu.utils.compat.device_peaks` —
            the CPU entry is empty, so MFU reads ``None`` there unless a
            test pins this.
        monitor: run the :class:`PerfMonitor` breach detection.
        slowdown_factor: rolling-median breach bound — the recent
            step-time median tripping ``factor ×`` the frozen baseline
            median raises ``warn reason=perf_regression``.
        mfu_collapse: MFU breach bound — recent median MFU falling under
            ``mfu_collapse ×`` the baseline median MFU raises the same warn
            (inactive where MFU is None, i.e. CPU).
        window: recent-median window (steps).
        baseline_steps: steps frozen into the baseline after ``skip_steps``.
        skip_steps: leading steps excluded from the baseline (step 1 carries
            the compile wall).
        capture: on a breach, capture one bounded ``jax.profiler`` window
            into ``<run_dir>/profile/perf_<iter>/`` (needs a run dir; warns
            still fire without one). Once per episode, re-arming.
        capture_steps: length of the capture window, in steps.
    """

    every_n_steps: int = 8
    cost: bool = True
    peak_flops: Optional[float] = None
    monitor: bool = True
    slowdown_factor: float = 1.75
    mfu_collapse: float = 0.5
    window: int = 8
    baseline_steps: int = 16
    skip_steps: int = 1
    capture: bool = True
    capture_steps: int = 4

    def __post_init__(self):
        if self.every_n_steps < 1:
            raise ValueError(
                f"every_n_steps must be >= 1, got {self.every_n_steps}"
            )
        if self.slowdown_factor <= 1.0:
            raise ValueError(
                f"slowdown_factor must be > 1, got {self.slowdown_factor}"
            )
        if not 0.0 < self.mfu_collapse < 1.0:
            raise ValueError(
                f"mfu_collapse must be in (0,1), got {self.mfu_collapse}"
            )
        if self.window < 2 or self.baseline_steps < 2:
            raise ValueError("window and baseline_steps must be >= 2")
        if self.capture_steps < 1:
            raise ValueError(
                f"capture_steps must be >= 1, got {self.capture_steps}"
            )


# --------------------------------------------------------------------------
# the monitor
# --------------------------------------------------------------------------

class PerfMonitor(MonitorBase):
    """Flags a run whose steps still complete, but SLOWER — the gap the
    :class:`~bigdl_tpu.obs.watchdog.StallWatchdog` (steps stopped entirely)
    and the divergence guard (loss went non-finite) both leave open.

    Baseline: after ``skip_steps`` warmup steps, the next
    ``baseline_steps`` walls (and MFU samples) freeze into a baseline
    median. Breach: the rolling median of the last ``window`` steps
    exceeding ``slowdown_factor ×`` the baseline (or the MFU median falling
    under ``mfu_collapse ×`` its baseline) raises ONE event per episode —
    re-armed when the medians recover, so a relapse raises again. Each
    event names the **degraded component**: the compute/comms/input/host
    decomposition term with the largest mean increase over its baseline.

    Shaped for tests like every monitor on the
    :class:`~bigdl_tpu.obs.watchdog.MonitorBase` chassis: detection is a
    pure function of the recorded samples — drive :meth:`note_step`
    directly, no thread, no sleeps, no real clock (the injected ``clock``
    only timestamps capture bookkeeping)."""

    def __init__(self, config: Optional[PerfConfig] = None,
                 clock=time.monotonic, poll_interval_s: float = 5.0):
        super().__init__(poll_interval_s)
        self.config = config or PerfConfig()
        self._clock = clock
        self._lock = threading.Lock()
        self.event_count = 0
        self.reset_run()

    def reset_run(self) -> None:
        """Per-run reset (a reused accountant across two fits must not judge
        run 2 by run 1's baseline)."""
        cfg = self.config
        with self._lock:
            self._seen = 0
            self._baseline_walls: List[float] = []
            self._baseline_mfus: List[float] = []
            self._baseline_comp: List[Dict] = []
            self._recent_walls: collections.deque = collections.deque(
                maxlen=cfg.window
            )
            self._recent_mfus: collections.deque = collections.deque(
                maxlen=cfg.window
            )
            self._recent_comp: collections.deque = collections.deque(
                maxlen=cfg.window
            )
            self._breached = False

    # ------------------------------------------------------------ recording
    def note_step(self, *, iteration: int, wall_s: float,
                  mfu_value: Optional[float] = None,
                  breakdown: Optional[Dict] = None) -> List[Dict]:
        """Record one completed step; returns the breach events raised BY
        this step (at most one — once per episode)."""
        cfg = self.config
        with self._lock:
            self._seen += 1
            if self._seen <= cfg.skip_steps:
                return []
            if len(self._baseline_walls) < cfg.baseline_steps:
                self._baseline_walls.append(float(wall_s))
                if mfu_value is not None:
                    self._baseline_mfus.append(float(mfu_value))
                if breakdown:
                    self._baseline_comp.append(dict(breakdown))
                return []
            self._recent_walls.append(float(wall_s))
            if mfu_value is not None:
                self._recent_mfus.append(float(mfu_value))
            if breakdown:
                self._recent_comp.append(dict(breakdown))
            if len(self._recent_walls) < cfg.window:
                return []
            return self._evaluate(iteration)

    # ------------------------------------------------------------- checking
    def baseline_wall_s(self) -> Optional[float]:
        with self._lock:
            if len(self._baseline_walls) < self.config.baseline_steps:
                return None
            return statistics.median(self._baseline_walls)

    def _breach_condition(self):
        """Pure read of the current breach condition over the recorded
        samples (lock held, NO state mutation): ``(trigger, detail)`` or
        ``(None, {})``."""
        cfg = self.config
        base = statistics.median(self._baseline_walls)
        recent = statistics.median(self._recent_walls)
        if base > 0 and recent > cfg.slowdown_factor * base:
            return "step_time", {
                "recent_wall_s": round(recent, 6),
                "baseline_wall_s": round(base, 6),
                "factor": round(recent / base, 3),
            }
        if (
            len(self._baseline_mfus) >= 2
            and len(self._recent_mfus) >= max(2, cfg.window // 2)
        ):
            bm = statistics.median(self._baseline_mfus)
            rm = statistics.median(self._recent_mfus)
            if bm > 0 and rm < cfg.mfu_collapse * bm:
                return "mfu_collapse", {
                    "recent_mfu": round(rm, 6),
                    "baseline_mfu": round(bm, 6),
                    "collapse": round(rm / bm, 4),
                }
        return None, {}

    def _evaluate(self, iteration: int) -> List[Dict]:
        """Breach test + episode latch (lock held) — the ONE place the
        once-per-episode state advances, owned by :meth:`note_step`."""
        trigger, detail = self._breach_condition()
        if trigger is None:
            self._breached = False  # recovered: re-arm the episode
            return []
        if self._breached:
            return []  # already warned for THIS episode
        self._breached = True
        self.event_count += 1
        event = {
            "reason": "perf_regression",
            "trigger": trigger,
            "iteration": int(iteration),
            "component": self._degraded_component(),
        }
        event.update(detail)
        return [event]

    def _degraded_component(self) -> Optional[str]:
        """Name the decomposition term with the largest mean increase over
        its baseline — what the ``warn`` record blames."""
        if not self._baseline_comp or not self._recent_comp:
            return None

        def means(rows: List[Dict]) -> Dict[str, float]:
            out = {}
            for key in COMPONENTS:
                vals = [r.get(key) or 0.0 for r in rows]
                out[key] = sum(vals) / len(vals)
            return out

        base = means(list(self._baseline_comp))
        recent = means(list(self._recent_comp))
        worst, worst_delta = None, 0.0
        for key in COMPONENTS:
            delta = recent[key] - base[key]
            if delta > worst_delta:
                worst, worst_delta = key, delta
        return worst[: -len("_s")] if worst else None

    def check(self) -> List[Dict]:
        """MonitorBase poll hook: a READ-ONLY probe of the current breach
        condition. Deliberately no episode latching here — the poll thread
        discards ``check()``'s return value, so a mutating check would
        silently consume the once-per-episode event and the driver's
        :meth:`note_step` (which owns warn emission + capture) would never
        see it. Returns the condition as an un-latched event list so a
        standalone caller can still poll state."""
        with self._lock:
            if (
                len(self._baseline_walls) < self.config.baseline_steps
                or len(self._recent_walls) < self.config.window
            ):
                return []
            trigger, detail = self._breach_condition()
            if trigger is None:
                return []
            event = {
                "reason": "perf_regression",
                "trigger": trigger,
                "iteration": int(self._seen),
                "component": self._degraded_component(),
            }
            event.update(detail)
            return [event]


# --------------------------------------------------------------------------
# the accountant
# --------------------------------------------------------------------------

class PerfAccountant:
    """The always-on perf surface of one optimizer (docs/performance.md).

    Owned by the :class:`~bigdl_tpu.optim.local_optimizer.Optimizer` and
    driven entirely from the one-step-late flush seam the driver loop
    already runs — zero new device syncs, and with no telemetry attached
    nothing here executes at all:

    * :meth:`ensure_cost` — once per compiled step, derive the program cost
      (:func:`program_cost`) from the jitted fn + its captured input specs;
    * :meth:`step_fields` — the ``model_flops`` / ``achieved_flops_s`` /
      ``mfu`` stamps for each ``step`` record;
    * :meth:`note_step` — fold the emitted record into the decomposition
      window, feed the :class:`PerfMonitor`, and manage the bounded breach
      capture; returns the ``warn`` payloads to emit;
    * :meth:`perf_fields` — the windowed ``perf`` record every
      ``every_n_steps`` steps.
    """

    def __init__(self, config: Optional[PerfConfig] = None):
        self.config = config or PerfConfig()
        self.monitor = (
            PerfMonitor(self.config) if self.config.monitor else None
        )
        self.cost: Optional[StepCost] = None
        # STRONG reference to the jitted step the cost was derived for (the
        # owning Optimizer pins the current step anyway): identity compared
        # with `is`, never id() — a freed fn's address can be reused by the
        # next build, which would silently stamp the new program with the
        # stale program's cost
        self._cost_fn = None
        # GPipe schedule stamp (None off the pipeline paths): like the cost,
        # a property of the compiled program — set by the pipeline optimizer
        # when it resolves (S, n_micro), NOT reset per run, so a retry that
        # reuses the cached step keeps its schedule accounting
        self.pipe_bubble_frac: Optional[float] = None
        self._n_devices = 1
        self._peaks = None  # compat.DevicePeaks | None, resolved per run
        self._window_rows: List[Dict] = []
        self._steps = 0
        self.captures = 0
        self._capture_left = 0

    # ------------------------------------------------------------ lifecycle
    def begin_run(self, n_devices: int = 1) -> None:
        """Reset per-run state at ``run_started`` (the derived cost is keyed
        by step identity and survives retries — a resumed attempt that hits
        the cached step re-derives nothing)."""
        from ..utils.compat import device_peaks

        self._n_devices = max(1, int(n_devices))
        self._peaks = device_peaks()
        self._window_rows = []
        self._steps = 0
        if self.monitor is not None:
            self.monitor.reset_run()

    def end_run(self) -> None:
        """Close out a run: a breach capture still open (the run ended
        mid-window) is stopped so the trace flushes and the next run's
        profiler starts clean."""
        if self._capture_left > 0:
            self._capture_left = 0
            stop_capture()

    # ----------------------------------------------------------------- cost
    def peak_flops(self) -> Optional[float]:
        if self.config.peak_flops is not None:
            return self.config.peak_flops
        return self._peaks.flops if self._peaks is not None else None

    def ensure_cost(self, fn, export_info) -> None:
        """Derive the step's cost model once per (jitted fn) — called at the
        first one-step-late flush, while the device executes the step the
        driver just dispatched. ``export_info`` is the optimizer's captured
        ``(fn, specs)`` pair (the AOT export seam's metadata)."""
        if not self.config.cost or os.environ.get("BIGDL_PERF_COST") == "0":
            return
        if fn is None or export_info is None or export_info[0] is not fn:
            return
        if fn is self._cost_fn:
            return  # derived (or definitively failed) for THIS program
        self._cost_fn = fn
        self.cost = program_cost(fn, export_info[1])

    def note_pipeline_schedule(self, n_stages: int, n_micro: int) -> None:
        """Stamp the GPipe schedule's theoretical idle fraction
        (:func:`pipeline_bubble_fraction`) onto every subsequent step/perf
        record — the observable the pipeline optimizer publishes so a bad
        ``n_micro`` choice shows up in telemetry, not just in wall time."""
        self.pipe_bubble_frac = round(
            pipeline_bubble_fraction(n_stages, n_micro), 6
        )

    # ----------------------------------------------------------- step seams
    def step_fields(self, wall_s: Optional[float]) -> Dict:
        """The per-step record stamps. Empty before the cost is known (or
        with ``cost=False``); ``mfu`` None wherever the backend has no peak
        entry — every field is None-graceful by contract."""
        c = self.cost
        if c is None or not c.flops:
            if self.pipe_bubble_frac is not None:
                # schedule stamp is cost-model independent: it must land even
                # where the backend reports no flops
                return {"pipe_bubble_frac": self.pipe_bubble_frac}
            return {}
        ach = achieved_flops_s(c.flops, wall_s)
        out = {
            "model_flops": c.flops,
            "achieved_flops_s": None if ach is None else round(ach, 3),
            "mfu": mfu(c.flops, wall_s, self.peak_flops(), self._n_devices),
        }
        if self.pipe_bubble_frac is not None:
            out["pipe_bubble_frac"] = self.pipe_bubble_frac
        return out

    def _breakdown(self, rec: Dict) -> Dict:
        """One step's compute/comms/input/host decomposition from fields the
        record already carries (host clocks only): ``input_s`` is the
        prefetch worker's wait for this batch, ``host_s`` the driver-thread
        dispatch seam, ``comms_s`` the wire-time estimate (collective
        operand bytes over the interconnect peak — None off-TPU), and
        ``compute_s`` the remainder of the step wall."""
        wall = rec.get("wall_s") or 0.0
        input_s = rec.get("input_wait_s") or 0.0
        # host seam from the record's drained dispatch SPAN, not the
        # dispatch_s field: at the one-step-late flush the wall covers the
        # interval up to the NEXT dispatch, and the drained spans cover the
        # same interval — the field lags it by one step, which would blame
        # "compute" for the first slow dispatch of an episode
        spans = rec.get("spans") or {}
        d = spans.get("dispatch")
        host_s = float(d["s"]) if d else (rec.get("dispatch_s") or 0.0)
        comms_s = None
        c = self.cost
        if (
            c is not None and c.collective_bytes and self._n_devices > 1
            and self._peaks is not None and self._peaks.ici_bytes_s
        ):
            comms_s = c.collective_bytes / self._peaks.ici_bytes_s
        compute_s = max(wall - input_s - host_s - (comms_s or 0.0), 0.0)
        return {
            "compute_s": round(compute_s, 6),
            "comms_s": None if comms_s is None else round(comms_s, 6),
            "input_s": round(input_s, 6),
            "host_s": round(host_s, 6),
        }

    def note_step(self, rec: Dict) -> List[Dict]:
        """Fold one emitted ``step`` record into the window + monitor;
        returns the ``warn`` payloads (perf_regression breaches) the caller
        should emit. Manages the bounded breach capture: started on a breach
        (when a run dir resolves), stopped ``capture_steps`` steps later."""
        self._steps += 1
        breakdown = self._breakdown(rec)
        self._window_rows.append({
            "wall_s": rec.get("wall_s") or 0.0,
            "mfu": rec.get("mfu"),
            "breakdown": breakdown,
        })
        if self._capture_left > 0:
            self._capture_left -= 1
            if self._capture_left == 0:
                stop_capture()
        events: List[Dict] = []
        if self.monitor is not None:
            events = self.monitor.note_step(
                iteration=rec.get("iteration") or self._steps,
                wall_s=rec.get("wall_s") or 0.0,
                mfu_value=rec.get("mfu"),
                breakdown=breakdown,
            )
            for ev in events:
                ev["capture_dir"] = self._maybe_capture(ev)
        return events

    def _maybe_capture(self, event: Dict) -> Optional[str]:
        """One bounded profiler window per breach episode, under
        ``<run_dir>/profile/perf_<iteration>/``. Skipped (warn still fires)
        without a run dir, while another capture runs (a ``set_profile``
        window holds the profiler), or when disabled."""
        if not self.config.capture or self._capture_left > 0:
            return None
        from ..utils.engine import Engine

        base = Engine.run_subdir("profile")
        if base is None:
            return None
        trace_dir = os.path.join(
            base, f"perf_{int(event.get('iteration') or 0):06d}"
        )
        if not start_capture(trace_dir):
            return None
        log.warning(
            "perf regression (%s, component=%s) at iteration %s: capturing "
            "%d-step profiler trace into %s",
            event.get("trigger"), event.get("component"),
            event.get("iteration"), self.config.capture_steps, trace_dir,
        )
        self.captures += 1
        self._capture_left = self.config.capture_steps
        return trace_dir

    # --------------------------------------------------------- perf records
    def should_emit(self) -> bool:
        return self._steps > 0 and self._steps % self.config.every_n_steps == 0

    def perf_fields(self) -> Dict:
        """Drain the window into one ``perf`` record's fields (schema:
        docs/observability.md): windowed wall mean, the cost-model join
        (model flops / achieved / MFU / roofline bound), and the mean
        compute/comms/input/host decomposition."""
        rows, self._window_rows = self._window_rows, []
        n = len(rows)
        wall_mean = sum(r["wall_s"] for r in rows) / n if n else 0.0
        breakdown = {}
        for key in COMPONENTS:
            vals = [r["breakdown"].get(key) for r in rows]
            known = [v for v in vals if v is not None]
            breakdown[key] = (
                round(sum(known) / len(known), 6) if known else None
            )
        c = self.cost
        peak = self.peak_flops()
        hbm = self._peaks.hbm_bytes_s if self._peaks is not None else None
        ach = achieved_flops_s(c.flops if c else None, wall_mean)
        out = {
            "window": n,
            "wall_mean_s": round(wall_mean, 6),
            "breakdown": breakdown,
            "model_flops": c.flops if c else None,
            "achieved_flops_s": None if ach is None else round(ach, 3),
            "mfu": mfu(c.flops if c else None, wall_mean, peak,
                       self._n_devices),
            "arithmetic_intensity": (
                c.arithmetic_intensity if c else None
            ),
            "bound": classify_roofline(
                c.arithmetic_intensity if c else None, peak, hbm
            ),
            "collective_bytes": c.collective_bytes if c else None,
            "hbm_bytes_accessed": c.bytes_accessed if c else None,
        }
        # pp/ep observables (PR 17): present whenever the program carries
        # the matching collectives (or a pipeline schedule was noted), so
        # obs_report's perf section can render the parallelism's wire cost
        if c is not None and c.all_to_all_bytes:
            out["all_to_all_bytes"] = c.all_to_all_bytes
        if c is not None and c.ppermute_bytes:
            out["ppermute_bytes"] = c.ppermute_bytes
        if self.pipe_bubble_frac is not None:
            out["pipe_bubble_frac"] = self.pipe_bubble_frac
        return out
