"""bigdl_tpu.obs — unified telemetry layer (docs/observability.md).

Four pieces:

* :mod:`~bigdl_tpu.obs.telemetry` — per-step event stream: one structured
  record per step fanned out through pluggable exporters (JSONL file,
  TensorBoard via ``TrainSummary``, in-memory ring buffer), carrying loss /
  LR / throughput, dispatch+wall seconds, compile events, span timings and
  per-device HBM watermarks — with ZERO new host syncs;
* :mod:`~bigdl_tpu.obs.trace` — ``span("name")`` host-seam tracing bridged to
  ``jax.profiler.TraceAnnotation`` + per-dispatch step annotations;
* :mod:`~bigdl_tpu.obs.watchdog` — :class:`StallWatchdog`, flags a run that
  stops completing steps;
* :mod:`~bigdl_tpu.obs.health` — :class:`HealthMonitor` (``set_health``):
  in-graph per-layer gradient/update/activation statistics, ``health``
  records, NaN root-cause attribution for divergence rollbacks;
* :mod:`~bigdl_tpu.obs.profiler` — one-shot per-layer HBM breakdown +
  HLO cost summary (``tools/health_report.py`` front-end);
* :mod:`~bigdl_tpu.obs.perf` — always-on MFU/roofline accounting
  (:class:`PerfAccountant`), per-step compute/comms/input/host
  decomposition on ``perf`` records, and the :class:`PerfMonitor`
  regression detector with bounded triggered profiler capture;
* :mod:`~bigdl_tpu.obs.fleet` — fleet identity (process-tagged records,
  per-process ``telemetry/p<k>.jsonl`` streams), atomic heartbeat files and
  the :class:`FleetMonitor` straggler/lost-host detector;
* :mod:`~bigdl_tpu.obs.export` — :class:`ObsEndpoint`, the device-free
  ``/healthz`` + ``/metrics`` + ``/telemetry/tail`` scrape surface
  (``Engine.set_metrics_port`` / ``ModelServer(metrics_port=)``);
* :mod:`~bigdl_tpu.obs.blackbox` — the always-on :class:`FlightRecorder`
  (per-type last-N rings teed off every Telemetry) and
  :func:`dump_postmortem`, the verified triage bundle every abnormal exit
  writes (``tools/postmortem.py`` renders them);
* ``tools/obs_report.py`` — offline summary of a run's JSONL stream(s),
  ``--fleet`` merging N per-process streams by (epoch, iteration).
"""

from .blackbox import (
    BundleTampered,
    BundleTruncated,
    FlightRecorder,
    PostmortemBundleError,
    arm_crash_handler,
    disarm_crash_handler,
    dump_postmortem,
    load_bundle,
    verify_bundle,
)
from .export import ObsEndpoint
from .fleet import FleetMonitor, process_identity, read_heartbeats, write_heartbeat
from .health import HealthConfig, HealthMonitor
from .perf import PerfAccountant, PerfConfig, PerfMonitor
from .profiler import cost_summary, memory_breakdown, profile_optimizer
from .telemetry import (
    JsonlExporter,
    Metrics,
    RingBufferExporter,
    SummaryExporter,
    Telemetry,
    TelemetryExporter,
    device_memory_stats,
)
from .trace import span, step_annotation
from .watchdog import StallWatchdog

__all__ = [
    "Telemetry",
    "TelemetryExporter",
    "JsonlExporter",
    "RingBufferExporter",
    "SummaryExporter",
    "device_memory_stats",
    "Metrics",
    "span",
    "step_annotation",
    "StallWatchdog",
    "FleetMonitor",
    "ObsEndpoint",
    "process_identity",
    "read_heartbeats",
    "write_heartbeat",
    "HealthConfig",
    "HealthMonitor",
    "PerfAccountant",
    "PerfConfig",
    "PerfMonitor",
    "memory_breakdown",
    "cost_summary",
    "profile_optimizer",
    "FlightRecorder",
    "PostmortemBundleError",
    "BundleTruncated",
    "BundleTampered",
    "arm_crash_handler",
    "disarm_crash_handler",
    "dump_postmortem",
    "verify_bundle",
    "load_bundle",
]
