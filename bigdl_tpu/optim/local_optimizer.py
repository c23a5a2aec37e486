"""Training orchestration: ``Optimizer`` facade + single-device ``LocalOptimizer``.

Reference behavior (SURVEY.md §2.4, §3.1): ``Optimizer[T](model, dataset,
criterion)`` with an endWhen trigger, checkpoint/validation/summary triggers;
``LocalOptimizer`` clones the model per core and aggregates thread-local grads;
``DistriOptimizer`` adds the BlockManager all-reduce.

TPU-native design: the entire per-iteration hot loop (forward, loss, backward,
optimizer update) is ONE jitted function — the reference's thread-level model
cloning disappears (the chip is one program), and the iteration log line / trigger
semantics are preserved exactly:
``[Epoch e][Iteration i][Wall t] loss is L, throughput is R records/s``.
"""

from __future__ import annotations

import collections
import contextlib
import logging
import math
import threading
import time
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..dataset.dataset import AbstractDataSet, MiniBatch, pad_minibatch
from ..nn.criterion import AbstractCriterion
from ..nn.module import AbstractModule
from ..obs import trace as obs_trace
from ..obs.trace import span as obs_span
from ..resilience.errors import (
    DivergenceError,
    ElasticRemesh,
    StallEscalation,
    TrainingPreempted,
)
from ..utils.random import RandomGenerator
from .metrics import Metrics
from .optim_method import OptimMethod, SGD
from .trigger import Trigger
from .validation import ValidationMethod, ValidationResult

log = logging.getLogger("bigdl_tpu.optim")

# Revision of the names a train step's ops carry: the module paths of
# ``nn.module.run_child`` and the step parts of the step builders
# (``model_apply``, ``criterion``, ``optim_update``, ``param_views``,
# ``grad_exchange``, ``param_gather``, ``state_sync``). The persistent compile
# cache keys a program WITHOUT its name stacks (jax strips locations from the
# key), so a hit serves the executable, and with it the op names a profile
# shows, of whichever compile wrote the entry. The revision is part of the
# step's program name, which IS in the key: bump it when the names change,
# or a profile of a cached step reads as the older program.
STEP_SCOPES_REV = "s2"


def step_program_name(fn):
    """``fn`` named ``<name>_<STEP_SCOPES_REV>`` for ``jax.jit``."""
    fn.__name__ = f"{fn.__name__}_{STEP_SCOPES_REV}"
    return fn


def _to_device_tree(x):
    """asarray over a pytree (features may be a Table holding SparseTensors)."""
    return jax.tree_util.tree_map(jnp.asarray, x)


def _host_nbytes(tree) -> int:
    """Bytes of a pytree's host leaves: what a ``device_put`` of it moves
    host→device (a leaf that is already a ``jax.Array`` crosses nothing)."""
    return sum(
        getattr(leaf, "nbytes", 0)
        for leaf in jax.tree_util.tree_leaves(tree)
        if not isinstance(leaf, jax.Array)
    )


def _host_buffer_free(placed, buf: np.ndarray) -> Optional[bool]:
    """May the host array ``buf`` be written again, now that it went through
    the placement seam as the device leaves ``placed``? Decided from what the
    leaves themselves show, not from the platform's name:

    - ``None``: not yet. A leaf is not ready, so its host→device copy may
      still be reading ``buf`` (the TPU client copies after the call returns).
    - ``False``: never. A leaf's memory lies inside ``buf`` (the CPU client
      does not copy a 64-byte-aligned numpy argument, it adopts it), or a leaf
      cannot be asked (a placement seam that returns something other than
      device arrays: it may be ``buf`` itself).
    - ``True``: every leaf is ready and lives elsewhere."""
    for leaf in placed:
        if not hasattr(leaf, "is_ready"):
            return False
        if not leaf.is_ready():
            return None
    lo = buf.ctypes.data
    hi = lo + buf.nbytes
    for leaf in placed:
        for shard in leaf.addressable_shards:
            if lo <= shard.data.unsafe_buffer_pointer() < hi:
                return False
    return True


class _DeviceBatch:
    """A MiniBatch whose arrays already live on device (built by the
    prefetcher). ``input_wait_s`` is the prefetch worker's wait for THIS
    batch from the upstream iterator (the host input pipeline's starvation
    signal); ``input_qdepth`` the pipeline staging-ring depth right after
    the pull (None when the upstream exposes no ring); ``h2d_bytes`` the
    bytes of its host leaves, which crossed host→device at the placement
    seam (None on a detached run, which counts nothing);
    ``host_buf_reused`` 1 when the dataset assembled it in a buffer handed
    back earlier, 0 when in fresh memory (None on a detached run and from a
    dataset that recycles nothing). ``trace`` is the
    batch's causal :class:`~bigdl_tpu.obs.trace.TraceContext` — the
    sanctioned carrier across the prefetch→driver thread seam (BDL022), so
    the driver's dispatch span chains onto the chunk's transform/place
    spans."""

    __slots__ = ("_x", "_t", "_n", "input_wait_s", "input_qdepth",
                 "h2d_bytes", "host_buf_reused", "trace")

    def __init__(self, x, t, n: int, input_wait_s: Optional[float] = 0.0,
                 input_qdepth: Optional[int] = None,
                 h2d_bytes: Optional[int] = None,
                 host_buf_reused: Optional[int] = None, trace=None):
        self._x, self._t, self._n = x, t, n
        self.input_wait_s = input_wait_s
        self.input_qdepth = input_qdepth
        self.h2d_bytes = h2d_bytes
        self.host_buf_reused = host_buf_reused
        self.trace = trace

    def get_input(self):
        return self._x

    def get_target(self):
        return self._t

    def size(self) -> int:
        return self._n


# process-wide gc-suspension token for the fit hot loop (see optimize()):
# a DEPTH COUNT, not a boolean — concurrent/nested fits each take a ticket,
# and collection resumes only when the LAST one returns. A plain
# isenabled() snapshot would let the first fit to finish re-enable gc while
# another fit's donated, cache-deserialized steps are still dispatching —
# exactly the mid-fit collection the guard exists to prevent.
_GC_GUARD_LOCK = threading.Lock()
_GC_GUARD = {"depth": 0, "was_enabled": False}


def _gc_guard_enter() -> None:
    import gc

    with _GC_GUARD_LOCK:
        _GC_GUARD["depth"] += 1
        if _GC_GUARD["depth"] == 1:
            _GC_GUARD["was_enabled"] = gc.isenabled()
            if _GC_GUARD["was_enabled"]:
                gc.disable()


def _gc_guard_exit() -> None:
    import gc

    with _GC_GUARD_LOCK:
        _GC_GUARD["depth"] -= 1
        if _GC_GUARD["depth"] == 0 and _GC_GUARD["was_enabled"]:
            gc.enable()


class Optimizer:
    """Facade holding model/dataset/criterion + run configuration; ``apply`` picks
    the concrete optimizer (reference: object Optimizer factory)."""

    def __init__(
        self,
        model: AbstractModule,
        dataset: AbstractDataSet,
        criterion: AbstractCriterion,
        validate: bool = True,
        donate: bool = True,
        comms_dtype=None,
        error_feedback: bool = True,
        master_dtype=None,
        slot_dtype=None,
    ):
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        # low-precision policy of the ZeRO-1 flat layout (the sharded
        # DistriOptimizer step; docs/performance.md): comms_dtype compresses
        # the flat gradient collective (bf16/fp8/int8 wire format with
        # per-segment scales + error feedback), slot_dtype stores the flat
        # optimizer slot vectors in bf16, master_dtype the master weight
        # vector in bf16. Resolved + validated HERE so a bad request (a
        # float8 master, fp8 on a stack without float8) dies with a clean
        # ValueError at construction, never mid-trace; every other path
        # refuses an active policy when its fit starts.
        from .quantization import LowPrecisionPolicy

        _pol = LowPrecisionPolicy(
            comms_dtype=comms_dtype, error_feedback=error_feedback,
            master_dtype=master_dtype, slot_dtype=slot_dtype,
        )
        self._precision = _pol if _pol.active else None
        self._state_prec = None  # StatePrecision bound to the run's codec
        self._compressor = None  # GradCompressor bound to the run's codec
        # fail-fast static analysis (bigdl_tpu.analysis): structural graph
        # checks now, ShapeProp against the first batch spec + ParamAudit in
        # _optimize_impl — all BEFORE any trace/XLA compile. validate=False
        # is the escape hatch.
        self.validate = validate
        # donate=True hands params/slots/model_state buffers to XLA each step
        # (in-place weight update: no params+slots shadow copy in HBM, half
        # the weight traffic). donate=False is the escape hatch for callers
        # that hold references to pre-step parameter arrays across a step.
        self.donate = donate
        # ragged-batch seam policy: pad-and-mask when the criterion exposes a
        # per-sample decomposition AND the model couples rows across the
        # batch only through the criterion, else drop (reference semantics).
        # Pads are masked out of the LOSS exactly, but they still pass
        # through the forward — BatchNorm batch/running statistics and
        # batch-derived auxiliary losses (MoE load balancing) would silently
        # absorb the repeated pad row, so those models keep the exact drop
        # semantics. The model half of the check needs the BUILT module tree
        # (keras wrappers materialize children at build), so the policy is
        # resolved in _make_standard_step; only the criterion half is fixed
        # here.
        self._criterion_maskable = bool(
            getattr(criterion, "supports_unreduced", lambda: False)()
        )
        self._mask_ragged = False  # resolved post-build in _make_standard_step
        self._step_rows: Optional[int] = None  # static rows of the jitted step
        self._jit_step = None  # handle for compile-count introspection/tests
        from ..utils.engine import Engine

        Engine.ensure_compilation_cache()  # persistent XLA compile cache
        if validate:
            self._validate_at_construction()
        self.optim_method: OptimMethod = SGD()
        self.end_when: Trigger = Trigger.max_epoch(1)
        self.validation_trigger: Optional[Trigger] = None
        self.validation_dataset: Optional[AbstractDataSet] = None
        self.validation_methods: Optional[Sequence[ValidationMethod]] = None
        self.checkpoint_path: Optional[str] = None
        self.checkpoint_trigger: Optional[Trigger] = None
        self.summary = None  # TrainSummary
        self.val_summary = None
        self.metrics = Metrics()
        self.telemetry = None  # obs.Telemetry sink (set_telemetry)
        self.health = None  # obs.HealthMonitor (set_health)
        # always-on perf accounting (obs/perf.py): MFU/roofline stamps on
        # every step record, windowed perf records, and the PerfMonitor
        # regression detector — active whenever telemetry is attached; a
        # detached fit executes none of it. set_perf customizes/disables.
        from ..obs.perf import PerfAccountant

        self._perf = PerfAccountant()
        self._compiles_seen = 0  # jit-cache entries already reported
        self._grad_clip_norm: Optional[float] = None
        self._grad_clip_const: Optional[tuple] = None
        # failure semantics (reference: Spark task retry + bigdl.failure.retryTimes)
        import os as _os

        self.retry_times: int = int(_os.environ.get("BIGDL_FAILURE_RETRY_TIMES", "0"))
        self._restored_flat_slots: Optional[Dict] = None
        self._resume_skip_iters: int = 0
        # resilience runtime (docs/resilience.md): FailurePolicy replaces the
        # bare retry loop; None = legacy retry_times shim (or no retries)
        self.failure_policy = None
        self.checkpoint_keep_last: Optional[int] = None
        self._preemption_guard = None
        self._active_policy = None  # the policy driving the CURRENT optimize()
        self._entry_snapshot: Optional[Dict] = None  # step-0 state (satellite fix)
        self._stall_cb_watchdog = None  # watchdog our stall forwarder is on
        self._compiles_fn = None  # jit fn the compile watermark belongs to
        self._step_cache = None  # (method, n_micro, jitted step) across retries
        self._prefetch_thread = None  # live prefetch worker (tests/shutdown)
        self._host_buffers = None  # the dataset's HostBuffers, once seen
        # FlatParameter codecs keyed by n_shards — kept across retries AND
        # elastic remeshes, so a rejoin back to a previously-seen mesh
        # configuration reuses its codec (and the jitted programs below)
        self._flat_fp: Dict[int, object] = {}
        self._counter_step = (None, ())  # (standard step, its counters' names)
        self._counter_names = ()
        # jitted (flatten, unflatten, slots_tree_view) per codec identity
        self._flat_jit: Dict[int, tuple] = {}
        # AOT step-artifact seam (utils/aot.py): (jitted step, arg spec tree)
        # captured at the first dispatch of a fit — what export_step_artifact
        # serializes so a preempted run resumed on a fresh host replays its
        # compiles as cache reads
        self._step_export_info = None
        self._warm_start_bundle = None  # artifact bundle this run seeded from
        self._cache_watch = None  # persistent-cache watch (compile cache_hit)
        # elastic fleet runtime (docs/resilience.md "Elastic fleet"):
        # coordinator attached via set_elastic; _fleet_writer is registered
        # by the flat/ZeRO-1 step builder each _optimize_impl entry and
        # routes _write_checkpoint onto the per-host-sharded fleet format;
        # _dataset_base keeps the UNSLICED dataset so reader re-sharding
        # after a remesh always slices from the original stream
        self._elastic = None
        self._fleet_writer = None
        self._dataset_base = None

    # ----------------------------------------------------------- configuration
    def set_optim_method(self, method: OptimMethod) -> "Optimizer":
        self.optim_method = method
        return self

    def set_end_when(self, trigger: Trigger) -> "Optimizer":
        self.end_when = trigger
        return self

    def set_validation(
        self,
        trigger: Trigger,
        dataset: AbstractDataSet,
        methods: Sequence[ValidationMethod],
    ) -> "Optimizer":
        self.validation_trigger = trigger
        self.validation_dataset = dataset
        self.validation_methods = list(methods)
        return self

    def set_checkpoint(self, path: Optional[str] = None,
                       trigger: Optional[Trigger] = None,
                       keep_last: Optional[int] = None) -> "Optimizer":
        """``path=None`` resolves to ``<run_dir>/checkpoints`` under the
        Engine run-dir convention (docs/observability.md layout).
        ``keep_last=N`` prunes all but the N newest checkpoints after each
        save (docs/resilience.md retention policy); None keeps everything."""
        if trigger is None:
            raise ValueError("set_checkpoint needs a trigger")
        if path is None:
            from ..utils.engine import Engine

            path = Engine.run_subdir("checkpoints")
            if path is None:
                raise ValueError(
                    "set_checkpoint() needs a path (or a run dir via "
                    "Engine.set_run_dir / BIGDL_RUN_DIR to default under)"
                )
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        self.checkpoint_keep_last = keep_last
        return self

    def set_train_summary(self, summary) -> "Optimizer":
        self.summary = summary
        return self

    def set_val_summary(self, summary) -> "Optimizer":
        self.val_summary = summary
        return self

    def set_telemetry(self, telemetry) -> "Optimizer":
        """Attach an :class:`~bigdl_tpu.obs.Telemetry` sink: one structured
        record per step (loss, LR, throughput, wall/dispatch seconds, compile
        events, span timings, HBM watermarks) fanned out to its exporters —
        docs/observability.md. All fields derive from host-side state the
        driver already holds, so attaching telemetry adds zero device syncs."""
        self.telemetry = telemetry
        return self

    def set_health(self, config=True) -> "Optimizer":
        """Attach model-health monitoring (docs/observability.md): the jitted
        train step additionally computes a compact per-layer statistics tree
        IN-GRAPH (grad/weight norms, update/weight ratio, non-finite counts,
        optional activation stats via forward hooks), pulled host-side at the
        same one-step-late seam as the loss — zero new device syncs, and the
        step still compiles exactly once. Stats fan out as ``health``
        telemetry records every ``every_n_steps`` steps; the divergence guard
        uses the per-layer non-finite counts to name the poisoned layer in
        its ``rollback`` record.

        ``config`` is a :class:`~bigdl_tpu.obs.HealthConfig` (or ``True`` for
        defaults, ``None``/``False`` to detach). Detached, the step program
        is bit-identical to a build without health support."""
        from ..obs.health import HealthConfig, HealthMonitor

        if self.health is not None and self.health is not config:
            # a previous monitor may have activation hooks installed — undo
            # them (and their seeded state entries) or the "detached" step
            # would keep paying for them and carry '_health_act' in state
            self.health.remove_hooks()
        if config is None or config is False:
            self.health = None
        elif isinstance(config, HealthMonitor):
            self.health = config
        elif isinstance(config, HealthConfig):
            self.health = HealthMonitor(config)
        elif config is True:
            self.health = HealthMonitor(HealthConfig())
        else:
            raise TypeError(
                f"set_health expects HealthConfig/HealthMonitor/bool, "
                f"got {type(config).__name__}"
            )
        # the step's output signature changes with health on/off: drop any
        # cached jitted step so the next optimize() rebuilds consistently
        self._step_cache = None
        return self

    def set_perf(self, config=True) -> "Optimizer":
        """Configure the always-on performance accounting (obs/perf.py,
        docs/performance.md "reading MFU and the roofline"). On by default
        whenever telemetry is attached: every ``step`` record is stamped
        with ``model_flops`` / ``achieved_flops_s`` / ``mfu`` (cost derived
        ONCE per compile through the sanctioned ``obs/profiler`` seam —
        zero new host syncs), a ``perf`` record with the compute/comms/
        input/host decomposition lands every ``every_n_steps`` steps, and
        the :class:`~bigdl_tpu.obs.PerfMonitor` raises
        ``warn reason=perf_regression`` (+ one bounded profiler capture
        under ``<run_dir>/profile/``) on a step-time or MFU breach.

        ``config`` is a :class:`~bigdl_tpu.obs.PerfConfig` (or a prebuilt
        :class:`~bigdl_tpu.obs.PerfAccountant`, or ``True`` for defaults,
        ``None``/``False`` to disable)."""
        from ..obs.perf import PerfAccountant, PerfConfig

        if config is None or config is False:
            self._perf = None
        elif isinstance(config, PerfAccountant):
            self._perf = config
        elif isinstance(config, PerfConfig):
            self._perf = PerfAccountant(config)
        elif config is True:
            self._perf = PerfAccountant()
        else:
            raise TypeError(
                f"set_perf expects PerfConfig/PerfAccountant/bool, "
                f"got {type(config).__name__}"
            )
        return self

    def _perf_device_count(self) -> int:
        """Chips participating in one step — the MFU denominator's device
        factor. The local path runs one device; the SPMD optimizers
        override with their mesh size."""
        return 1

    def _install_health(self) -> None:
        """Install the monitor's activation hooks on the BUILT model (must
        run before the state pytree is read for the step — the seeded
        zero entries are part of the traced input structure)."""
        if self.health is not None:
            self.health.prepare(self.model)

    def set_micro_batches(self, n: int) -> "Optimizer":
        """Split each batch into ``n`` microbatches inside the jitted step
        (``lax.scan`` accumulating gradients, one optimizer update) —
        the single-chip analog of the reference ParallelOptimizer's
        thread-level sub-batch gradient aggregation
        ($DL/optim/ParallelOptimizer's subModelNumber split), and an HBM
        lever: peak activation memory scales with the microbatch, not the
        batch. Math note: gradients are exactly the full-batch mean (up
        to float associativity) for mean-reduced losses; BatchNorm
        statistics become microbatch-local (ghost batch norm)."""
        if n < 1:
            raise ValueError(f"micro batch count must be >= 1, got {n}")
        self._micro_batches = int(n)
        return self

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float) -> "Optimizer":
        self._grad_clip_norm = float(clip_norm)
        return self

    def set_constant_gradient_clipping(self, min_v: float, max_v: float) -> "Optimizer":
        self._grad_clip_const = (float(min_v), float(max_v))
        return self

    # --------------------------------------------------------------- factory
    @staticmethod
    def apply(model, dataset, criterion) -> "Optimizer":
        from ..dataset.dataset import DistributedDataSet

        if isinstance(dataset, DistributedDataSet):
            try:
                from ..parallel.distri_optimizer import DistriOptimizer
            except ImportError as e:  # pragma: no cover
                raise NotImplementedError(
                    "DistriOptimizer is provided by bigdl_tpu.parallel"
                ) from e
            return DistriOptimizer(model, dataset, criterion)
        return LocalOptimizer(model, dataset, criterion)

    def set_profile(self, trace_dir: Optional[str] = None,
                    start_iteration: int = 10,
                    num_iterations: int = 5) -> "Optimizer":
        """Capture a ``jax.profiler`` device trace for a step window
        (reference: the ``*Perf`` drivers' step-breakdown role, SURVEY.md §5
        tracing row). View with TensorBoard's profile plugin or Perfetto.
        ``trace_dir=None`` resolves to ``<run_dir>/profile`` under the
        Engine run-dir convention (``Engine.set_run_dir`` / ``BIGDL_RUN_DIR``)
        so traces land beside the run's telemetry and checkpoints."""
        if trace_dir is None:
            from ..utils.engine import Engine

            trace_dir = Engine.run_subdir("profile")
            if trace_dir is None:
                raise ValueError(
                    "set_profile() needs a trace_dir (or a run dir via "
                    "Engine.set_run_dir / BIGDL_RUN_DIR to default under)"
                )
        self._profile = {"dir": trace_dir, "start": start_iteration,
                         "len": num_iterations}
        return self

    def set_retry_times(self, n: int) -> "Optimizer":
        """N automatic resume-from-checkpoint attempts on step failure
        (reference: the ``bigdl.failure.retryTimes`` system property — SURVEY.md
        §5 failure row). Requires ``set_checkpoint``. This is the legacy knob:
        it maps onto ``FailurePolicy.legacy(n)`` (n total attempts, any fault,
        no backoff, divergence guard off); attach a full
        :class:`~bigdl_tpu.resilience.FailurePolicy` via
        :meth:`set_failure_policy` for classified budgets, backoff, the
        divergence guard and poison-batch skip."""
        self.retry_times = int(n)
        return self

    def set_failure_policy(self, policy) -> "Optimizer":
        """Attach a :class:`~bigdl_tpu.resilience.FailurePolicy` — fault
        classification (transient / poison_batch / divergence / stall),
        per-class retry budgets, exponential backoff with seeded jitter, the
        NaN/Inf divergence guard with rollback + LR backoff, and stall
        escalation (docs/resilience.md). Retries still require a checkpoint
        path (``set_checkpoint``) to restore from."""
        self.failure_policy = policy
        return self

    def set_preemption(self, signals=None) -> "Optimizer":
        """Handle preemption signals (default SIGTERM): the driver loop
        writes an emergency checkpoint at the next step boundary, emits a
        ``preempt_checkpoint`` telemetry record, and raises
        :class:`~bigdl_tpu.resilience.TrainingPreempted` (``exit_code == 0``)
        so the rescheduled run resumes via :meth:`resume` instead of losing
        everything since the last periodic checkpoint."""
        from ..resilience.preemption import PreemptionGuard

        self._preemption_guard = PreemptionGuard(signals)
        return self

    def set_elastic(self, config=True) -> "Optimizer":
        """Attach elastic data-parallel training (docs/resilience.md
        "Elastic fleet"): a :class:`~bigdl_tpu.obs.fleet.FleetMonitor`-driven
        coordinator that, on a lost host (stale heartbeat), writes a
        process-coordinated emergency fleet checkpoint at the next step
        boundary, reshards the flat master vector onto the survivors' shrunk
        mesh (one new compile per mesh configuration, cached for repeats),
        and re-expands the mesh at the next epoch boundary when the host's
        heartbeat returns. Requires ``set_checkpoint`` and a resharding-
        capable optimizer (DistriOptimizer's flat/ZeRO-1 layout, or
        HybridParallelOptimizer). ``config`` is an
        :class:`~bigdl_tpu.resilience.ElasticConfig` (or ``True`` for
        defaults, ``None``/``False`` to detach; a pre-built
        :class:`~bigdl_tpu.resilience.ElasticCoordinator` is accepted for
        tests that inject monitors/clocks)."""
        from ..resilience.elastic import ElasticConfig, ElasticCoordinator

        if config is None or config is False:
            self._elastic = None
        elif isinstance(config, ElasticCoordinator):
            self._elastic = config
        elif isinstance(config, ElasticConfig):
            self._elastic = ElasticCoordinator(config)
        elif config is True:
            self._elastic = ElasticCoordinator(ElasticConfig())
        else:
            raise TypeError(
                f"set_elastic expects ElasticConfig/ElasticCoordinator/bool, "
                f"got {type(config).__name__}"
            )
        return self

    def _supports_elastic(self) -> bool:
        """Whether this optimizer can reshard its training state onto a
        shrunk/re-expanded mesh (overridden by the parallel optimizers)."""
        return False

    def _effective_policy(self):
        if self.failure_policy is not None:
            return self.failure_policy
        if self.retry_times > 0:
            from ..resilience.policy import FailurePolicy

            return FailurePolicy.legacy(self.retry_times)
        return None

    def optimize(self) -> AbstractModule:
        """Train under the resilience runtime (docs/resilience.md): failures
        are classified by the attached :class:`FailurePolicy` (or the legacy
        ``retry_times`` shim) and retried within per-class budgets with
        backoff, restoring from the newest VERIFIED checkpoint — or from the
        step-0 entry snapshot when no checkpoint has been written yet.
        Divergence (NaN/Inf loss) rolls back to the newest *finite* verified
        checkpoint and backs off the LR; a pending preemption signal exits
        cleanly behind an emergency checkpoint."""
        policy = self._active_policy = self._effective_policy()
        if policy is not None:
            policy.reset()
        self._entry_snapshot = None
        guard = self._preemption_guard
        if guard is not None:
            guard.clear()
            guard.install()
        el = self._elastic
        self._fleet_writer = None  # re-registered by the elastic step builder
        if el is not None:
            if not self._supports_elastic():
                raise ValueError(
                    "elastic training (set_elastic) needs a resharding-"
                    "capable optimizer — DistriOptimizer's flat/ZeRO-1 "
                    f"layout or HybridParallelOptimizer; {type(self).__name__} "
                    "has no remesh path"
                )
            if self.checkpoint_path is None:
                raise ValueError(
                    "elastic training reshards through coordinated fleet "
                    "checkpoints; call set_checkpoint first"
                )
            from ..utils.engine import Engine

            el.bind(run_dir=Engine.run_dir(), telemetry=self.telemetry)
            el.start()
        self._apply_reader_slice()
        # Suspend CYCLE collection for the duration of the fit (refcount
        # frees are untouched; collection resumes organically once the LAST
        # concurrent fit returns — see _gc_guard_enter): CPython gc pauses
        # on the driver thread add jitter in front of every dispatch.
        # Deliberately NO forced gc.collect() here — deferred frees are
        # collected organically OUTSIDE fits.
        _gc_guard_enter()
        try:
            while True:
                remesh = None
                try:
                    return self._optimize_impl()
                except (KeyboardInterrupt, TrainingPreempted):
                    raise
                except ElasticRemesh as e:
                    remesh = e
                except Exception as e:
                    decision = self._decide_retry(e)
                    if decision is None:
                        # terminal: the policy is out of budget (or absent) and
                        # this exception is about to escape optimize() — leave
                        # a triageable artifact before the process unwinds
                        self._dump_postmortem_for(e, "optimize")
                        raise
                    self._recover(e, decision)
                if remesh is not None:
                    # applied OUTSIDE the except block: a chaos FaultInjected
                    # (or any real fault) inside the reshard/rejoin seam must
                    # surface typed, not be swallowed into the retry ladder
                    # as a nested-handler classification
                    self._apply_remesh(remesh)
        finally:
            _gc_guard_exit()
            if guard is not None:
                guard.uninstall()
            if el is not None:
                el.stop()
            self._active_policy = None

    def _optimize_impl(self) -> AbstractModule:
        raise NotImplementedError

    # ------------------------------------------------------ failure recovery
    def _failure_position(self, exc) -> Optional[tuple]:
        """(epoch, iter_in_epoch) the failure belongs to — the key the
        policy uses for poison-batch (fails-twice) detection. Exceptions
        that surfaced at the one-step-late loss pull carry the PENDING
        step's position (``_bigdl_position``, stamped in ``flush``): the
        live ``_iter_in_epoch`` already points at the batch dispatched
        AFTER the one that faulted."""
        tagged = getattr(exc, "_bigdl_position", None)
        if tagged is not None:
            return tuple(tagged)
        if isinstance(exc, DivergenceError):
            return exc.position
        if isinstance(exc, StallEscalation):
            return None  # a stall has no meaningful data position
        st = self.optim_method.state
        return (int(st.get("epoch", 1)), int(st.get("_iter_in_epoch", 0)))

    def _dump_postmortem_for(self, exc: BaseException, trigger: str) -> None:
        """Freeze the flight recorder into a verified bundle before an
        exception escapes this optimizer terminally (obs/blackbox.py;
        docs/observability.md "Flight recorder & postmortems"). Best-effort
        by contract: forensics never turn one failure into two."""
        try:
            from ..obs import blackbox

            blackbox.dump_postmortem(
                "%s_%s" % (trigger, type(exc).__name__),
                telemetry=self.telemetry,
                error=exc,
                checkpoint_dir=self.checkpoint_path,
            )
        except Exception:  # lint: disable=BDL007 the original failure is re-raised; the dump is best-effort
            pass

    def _decide_retry(self, exc):
        """Run the failure through the policy; None = re-raise (no policy,
        no checkpoint path to restore from, or budgets exhausted)."""
        policy = self._active_policy
        if policy is None or self.checkpoint_path is None:
            return None
        decision = policy.on_failure(exc, position=self._failure_position(exc))
        return decision if decision.retry else None

    def _recover(self, exc, decision) -> None:
        """Backoff, restore (checkpoint or step-0 snapshot, with resume
        failures fed back into the policy), then apply the per-class
        after-effects (LR backoff on divergence)."""
        policy, tel = self._active_policy, self.telemetry
        log.exception(
            "training failed (%s fault, attempt %d); recovering",
            decision.fault_class, decision.total_attempts,
        )
        if tel is not None:
            tel.retry_event(
                attempt=decision.total_attempts,
                fault_class=decision.fault_class,
                backoff_s=decision.backoff_s,
                error=repr(exc),
                path=type(self).__name__,
                skip_position=(
                    list(decision.skip_position)
                    if decision.skip_position else None
                ),
            )
        if decision.backoff_s > 0:
            time.sleep(decision.backoff_s)
        require_finite = isinstance(exc, DivergenceError)
        while True:
            try:
                restored = self._resume_from_checkpoint(
                    require_finite=require_finite
                )
                break
            except KeyboardInterrupt:
                raise
            except Exception as e2:  # the checkpoint-load seam can fault too
                d2 = policy.on_failure(e2, position=None)
                if not d2.retry:
                    # terminal: the resume itself is out of budget and this
                    # exception escapes optimize() without passing back
                    # through the driver loop's handler — dump here
                    self._dump_postmortem_for(e2, "resume")
                    raise
                log.exception(
                    "resume failed (%s fault, attempt %d); retrying resume",
                    d2.fault_class, d2.total_attempts,
                )
                if tel is not None:
                    tel.retry_event(
                        attempt=d2.total_attempts,
                        fault_class=d2.fault_class,
                        backoff_s=d2.backoff_s,
                        error=repr(e2),
                        path=type(self).__name__,
                        action="resume_retry",
                    )
                if d2.backoff_s > 0:
                    time.sleep(d2.backoff_s)
        if require_finite:
            # the restore skipped newer non-finite checkpoints; delete them
            # so a later PLAIN restore (transient fault during the replay)
            # cannot hand the poisoned weights straight back
            from ..utils.serialization import quarantine_nonfinite

            removed = quarantine_nonfinite(
                self.checkpoint_path, newer_than=restored
            )
            if removed:
                log.warning(
                    "quarantined non-finite checkpoint(s) %s newer than "
                    "restored step %s", removed, restored,
                )
        if isinstance(exc, DivergenceError):
            scale = policy.lr_scale()
            if scale != 1.0:
                # read by the driver loop: lr = schedule_lr * _lr_scale;
                # applied AFTER restore so the checkpointed pre-divergence
                # scale does not clobber the freshly backed-off one
                self.optim_method.state["_lr_scale"] = scale
            if tel is not None:
                tel.rollback_event(
                    reason="non_finite_loss",
                    restored_step=restored,
                    iteration=exc.iteration,
                    lr_scale=scale,
                    path=type(self).__name__,
                    # health attribution (None without set_health): the first
                    # non-finite layer path and whether grads or weights
                    # poisoned it — the rollback names its root cause
                    layer=getattr(exc, "layer", None),
                    source=getattr(exc, "source", None),
                    # hybrid mesh localization: the data shard whose rows
                    # carried the non-finite values (None elsewhere)
                    shard=getattr(exc, "shard", None),
                )

    def resume(self, checkpoint_path: Optional[str] = None) -> "Optimizer":
        """Restore params/slots/model state/RNG/data position from the newest
        VERIFIED checkpoint (e.g. the emergency checkpoint a preempted run
        wrote) so a following :meth:`optimize` continues the run exactly;
        builds the model from the dataset spec first when needed."""
        if checkpoint_path is not None:
            self.checkpoint_path = checkpoint_path
        if self.checkpoint_path is None:
            raise ValueError(
                "resume() needs a checkpoint path (set_checkpoint or argument)"
            )
        from ..utils.serialization import latest_checkpoint_step

        if latest_checkpoint_step(self.checkpoint_path) is None:
            # a typo'd/empty directory must fail loudly, not silently
            # retrain from scratch
            raise FileNotFoundError(
                f"resume(): no checkpoints under {self.checkpoint_path}"
            )
        if not self.model.is_built():
            self._build_for_resume()
        self._resume_from_checkpoint()
        return self

    def _build_for_resume(self) -> None:
        x0 = self._first_batch_input()
        self.model.build(RandomGenerator.next_key(), jax.eval_shape(lambda: x0))

    # ------------------------------------------------------- AOT artifacts
    def _capture_step_specs(self, train_step, args) -> None:
        """Record the cached step's input geometry at its first dispatch —
        metadata only (ShapeDtypeStructs), safe on donated buffers, and a
        single identity check per step thereafter. This is what
        :meth:`export_step_artifact` serializes."""
        info = self._step_export_info
        if info is not None and info[0] is train_step:
            return
        from ..utils.aot import spec_tree

        self._step_export_info = (train_step, spec_tree(args))

    def export_step_artifact(self, path: str) -> Dict:
        """Write the AOT artifact bundle for this optimizer's compiled train
        step (docs/serving.md "fleet cold-start", trainer half): the
        ``jax.export``-serialized step module (when expressible), every
        persistent-compile-cache entry of this process, and the verified
        manifest (written LAST). A preempted run restored onto a fresh host
        seeds its empty compile cache dir from the bundle
        (:meth:`warm_start`) and reaches step 1 with ZERO fresh compiles —
        the resume re-traces, but every XLA compile is a disk read.

        Call after (or during) a fit — the step must have dispatched at
        least once so its geometry is known."""
        info = self._step_export_info
        if info is None:
            raise RuntimeError(
                "export_step_artifact: no compiled train step to export — "
                "run optimize() (at least one step) first"
            )
        from ..utils import aot

        return aot.export_step_bundle(
            path, fn=info[0], specs=info[1], path_type=type(self).__name__,
            extra={"donate": self.donate},
        )

    def warm_start(self, path: str) -> Dict:
        """Verify a step-artifact bundle and seed this process's compile
        cache from it (``utils/aot.py`` verify-on-load: manifest + sha256 +
        environment fingerprint; mismatch raises
        :class:`~bigdl_tpu.utils.aot.ArtifactIncompatible`). The following
        :meth:`resume` + :meth:`optimize` then replay their compiles as
        cache reads; the run_start telemetry record carries the bundle path
        so the stream is self-describing."""
        from ..utils import aot

        # kind-checked: a serving bundle's cache entries cannot cover the
        # train step — accepting one would record warm_start=<path> while
        # every step compile runs cold, the silent fake the tri-state
        # freshness accounting exists to prevent
        manifest = aot.warm_start(path, kind="train_step")
        self._warm_start_bundle = path
        return manifest

    def _resume_from_checkpoint(self, require_finite: bool = False) -> Optional[int]:
        """Restore params/model-state/optimizer slots/host state/RNG/data
        position from the newest VERIFIED checkpoint under
        ``checkpoint_path`` (corrupt/truncated checkpoints are detected by
        their manifest and skipped for older verified ones;
        ``require_finite`` additionally rejects checkpoints holding NaN/Inf
        params — the divergence-rollback contract). Falls back to the step-0
        entry snapshot when no checkpoint exists yet. Returns the restored
        step, or None for a snapshot reset."""
        from ..utils.serialization import latest_checkpoint_step, load_checkpoint

        if latest_checkpoint_step(self.checkpoint_path) is None:
            self._restore_entry_snapshot()
            return None
        el = self._elastic
        try:
            with obs_span("checkpoint_load"):
                params, flat_slots, host, flat_model_state = load_checkpoint(
                    self.checkpoint_path,
                    params_like=self.model.get_parameters(),
                    require_finite=require_finite,
                    # fleet manifests written BEFORE the last coordinated
                    # remesh are stale (pre-shrink bounds): restore only the
                    # current generation or newer
                    min_generation=(el.generation if el is not None else None),
                )
        except FileNotFoundError:
            # every checkpoint was rejected (e.g. all hold non-finite
            # params under require_finite): reset to step 0 instead
            self._restore_entry_snapshot()
            return None
        self._commit_restored(
            params,
            flat_model_state,
            flat_slots,
            {k: v for k, v in host.items() if not k.startswith("_rng")},
            (host["_rng_seed"], host["_rng_counter"]),
            host.get("_iter_in_epoch", 0),
        )
        return int(host.get("neval", 0))

    def _commit_restored(self, params_tree, flat_model_state, flat_slots,
                         host_items, rng, skip_iters) -> None:
        """Single restore contract shared by checkpoint resume and the
        step-0 entry snapshot: params, model state (BN stats), optimizer
        slots (re-placed onto the fresh slots' committed shardings by
        ``_init_slots``), host state table, RNG position, and the mid-epoch
        data position the driver loop must skip to."""
        from ..utils.serialization import unflatten_to_like

        self.model.set_parameters(_to_device_tree(params_tree))
        cur_state = self.model.get_state()
        if flat_model_state and cur_state:
            self.model.set_state(
                _to_device_tree(unflatten_to_like(flat_model_state, cur_state))
            )
        self._restored_flat_slots = flat_slots
        state = self.optim_method.state
        for k, v in host_items.items():
            state[k] = v
        RandomGenerator.restore(rng[0], rng[1])
        self._resume_skip_iters = int(skip_iters)

    def _capture_entry_snapshot(self, params, model_state, slots) -> None:
        """Host copy of the step-0 state, taken right before the first
        dispatch of an ``optimize()`` call. This is the reset target when a
        retry fires before any checkpoint was written: the old behavior —
        "retrying from current state" — replayed from possibly-divergent
        weights with a drifted RNG stream and counted as recovery."""
        if (
            self._entry_snapshot is not None
            or self._active_policy is None
            or self.checkpoint_path is None
        ):
            return
        from ..utils.serialization import flatten_pytree

        def host_copy(tree):
            # one-shot pre-loop host copy, never per-iteration (np.array, not
            # asarray: the snapshot must not alias live buffers)
            return {k: np.array(v) for k, v in flatten_pytree(tree).items()}  # lint: disable=BDL005 runs once before the first dispatch

        self._entry_snapshot = {
            "params": host_copy(params),
            "model_state": host_copy(model_state or {}),
            "slots": host_copy(slots),
            "host": {
                k: v
                for k, v in self.optim_method.state.items()
                if isinstance(v, (int, float, str, bool)) or v is None
            },
            "rng": (RandomGenerator.get_seed(), RandomGenerator._counter),
        }

    def _restore_entry_snapshot(self) -> None:
        snap = self._entry_snapshot
        if snap is None:
            log.warning(
                "no checkpoint written yet under %s and no step-0 snapshot "
                "captured; retrying from current state",
                self.checkpoint_path,
            )
            return
        from ..utils.serialization import unflatten_to_like

        log.warning(
            "no checkpoint written yet under %s; resetting to the step-0 "
            "entry snapshot", self.checkpoint_path,
        )
        host_items = dict(snap["host"])
        # the failed attempt may have flipped this after the pre-loop
        # snapshot; it decides whether the epoch advances on restart
        host_items["_epoch_done"] = False
        self._commit_restored(
            unflatten_to_like(snap["params"], self.model.get_parameters()),
            snap["model_state"],
            dict(snap["slots"]),
            host_items,
            snap["rng"],
            host_items.get("_iter_in_epoch", 0),
        )

    def _init_slots(self, method, params_or_flat):
        """Fresh slots, or the checkpointed ones when resuming. Restored
        leaves are committed to the FRESH slots' placements: a resumed
        attempt must present the jitted step with the exact input layouts of
        attempt 1 (GSPMD-sharded slots on the hybrid path), or the resume
        silently recompiles the whole program."""
        from ..utils.serialization import unflatten_to_like

        slots = method.init_slots(params_or_flat)
        if self._restored_flat_slots is not None:
            restored = unflatten_to_like(self._restored_flat_slots, slots)

            def place(r, ref):
                a = jnp.asarray(r)
                if getattr(ref, "_committed", False):
                    # the fresh slot is COMMITTED (hybrid: zeros_like of a
                    # GSPMD-placed param inherits its NamedSharding): match
                    # it exactly
                    return jax.device_put(a, ref.sharding)
                # uncommitted fresh slot (local/replicated zeros_like):
                # committing the restored one would CHANGE the pjit signature
                # (UnspecifiedValue -> concrete sharding) and recompile
                return a

            slots = jax.tree_util.tree_map(place, restored, slots)
            self._restored_flat_slots = None
        return slots

    # ------------------------------------------------- flat master-state path
    def _flat_codec(self, params, n_shards: int):
        """The FlatParameter codec for one mesh configuration — keyed by
        shard count and reused across retry/resume attempts AND elastic
        remeshes (same geometry ⇒ the cached jitted step and flatten/
        unflatten programs all stay valid; a rejoin back to a prior mesh
        hits the cache instead of recompiling)."""
        fp = self._flat_fp.get(int(n_shards))
        if fp is None or not fp.matches(params):
            from ..parallel.parameter import FlatParameter

            fp = FlatParameter(params, n_shards)
            self._flat_fp[int(n_shards)] = fp
        return fp

    def _flat_fns(self, fp):
        """Cached jitted (flatten, unflatten, slots_tree_view) per codec.
        These serve the tree-view SEAMS only — entry flatten (once per
        optimize/resume), and checkpoint/validation/summary materialization —
        never the per-step hot loop. Codec objects live in ``_flat_fp``, so
        keying by identity is stable."""
        cached = self._flat_jit.get(id(fp))
        if cached is None or cached[0] is not fp:
            cached = self._flat_jit[id(fp)] = (
                fp, jax.jit(fp.flatten), jax.jit(fp.unflatten),
                jax.jit(fp.slots_tree_view),
            )
        return cached[1], cached[2], cached[3]

    def _init_flat_slots(self, method, fp):
        """Fresh flat slot vectors, or the checkpointed ones when resuming.
        Checkpoints persist slots in TREE view (the same layout every
        tree-path run writes, so manifests stay bit-compatible across
        flat↔tree representation switches); resume re-flattens each slot
        exactly once. Legacy flat-vector slot checkpoints — and the entry
        snapshot, which stores the run's live representation — are accepted
        as-is."""
        from ..utils.serialization import unflatten_to_like

        slots = method.init_slots(jnp.zeros((fp.padded_total,), jnp.float32))
        restored = self._restored_flat_slots
        if restored is None:
            return slots
        self._restored_flat_slots = None
        try:
            like = {
                k: self.model.get_parameters()
                if getattr(v, "shape", None) == (fp.padded_total,)
                else v
                for k, v in slots.items()
            }
            return jax.tree_util.tree_map(
                jnp.asarray, fp.slots_from_tree(unflatten_to_like(restored, like))
            )
        except KeyError:
            # legacy flat-vector layout: one vector per slot name
            return jax.tree_util.tree_map(
                jnp.asarray, unflatten_to_like(restored, slots)
            )

    def _precision_for(self, fp):
        """``(StatePrecision | None, GradCompressor | None)`` bound to this
        run's codec — cached with stable identity across retry/resume
        attempts, so the step caches (which close over these objects) stay
        valid and a resume re-dispatches into the already-compiled step."""
        pol = self._precision
        if pol is None:
            return None, None
        sp = None
        if pol.quantizes_state:
            sp = self._state_prec
            if sp is None or sp.fp is not fp:
                from .quantization import StatePrecision

                sp = self._state_prec = StatePrecision(fp, pol)
        comp = None
        if pol.comms_dtype is not None:
            comp = self._compressor
            if comp is None or comp.fp is not fp:
                from ..parallel.compression import GradCompressor

                comp = self._compressor = GradCompressor(fp, pol)
        return sp, comp

    def _flat_state_thunks(self, codec, box):
        """(get_params, get_slots) thunks for the cold seams of a flat-path
        run (checkpoint/validation/histograms/final sync): one jitted
        unflatten into the tree view — decoding any low-precision storage
        back to f32 first, so checkpoints stay tree-layout/f32 and
        bit-compatible with unquantized runs."""
        _, unflatten, slots_view = self._flat_fns(codec)
        sp = self._state_prec
        if self._precision is not None and sp is not None and sp.fp is codec:
            return (
                lambda: unflatten(sp.decode_master(box["params"])),
                lambda: slots_view(sp.decode_slots(box["slots"])),
            )
        return (
            lambda: unflatten(box["params"]),
            lambda: slots_view(box["slots"]),
        )

    def _wd_coefficients(self, method, fp):
        """Per-element weight-decay coefficient vector for the fused flat
        update, or None when the method's built-in uniform term suffices.
        Path-based exclusions (``weightdecay_exclude``) are the only case
        needing it: the flat layout carries no parameter names, so the
        exclusion mask is baked into a constant here, once."""
        wd = float(getattr(method, "weightdecay", 0.0) or 0.0)
        exclude = tuple(getattr(method, "weightdecay_exclude", ()) or ())
        if wd <= 0 or not exclude:
            return None
        return jnp.asarray(fp.coefficient_vector(
            lambda path: 0.0 if any(pat in path for pat in exclude) else wd
        ))

    # ------------------------------------------------------- static analysis
    def _validate_at_construction(self) -> None:
        """Structure-only checks that need no input spec: every Graph in the
        model tree is validated (cycles, duplicate names, merge arity), and a
        pre-built model's params are audited immediately."""
        from ..analysis import GraphValidator, ParamAudit
        from ..nn.graph import Graph

        for m in self.model.walk():
            if isinstance(m, Graph):
                GraphValidator(m).check()
        if self.model.is_built():
            ParamAudit(self.model).check()

    def _validate_before_step(self, x_spec) -> None:
        """ShapeProp the model against the actual batch spec — a bad model
        dies here with a module-path error instead of minutes later inside a
        mangled jit trace. Structure-only passes; the (device-to-host)
        ParamAudit runs exactly once, post-build, in ``_audit_params``."""
        if not self.validate:
            return
        from ..analysis import GraphValidator, ShapeProp
        from ..nn.graph import Graph

        for m in self.model.walk():
            if isinstance(m, Graph):
                GraphValidator(m).check()
        ShapeProp(self.model).infer(x_spec)

    def _audit_params(self) -> None:
        """Post-build parameter hygiene (aliasing, fp32 masters, finiteness)."""
        if not self.validate:
            return
        from ..analysis import ParamAudit

        ParamAudit(self.model).check()

    def _has_batch_coupled_state(self) -> bool:
        """True when the training forward couples rows across the batch
        outside the criterion: BatchNormalization-family batch statistics,
        or batch-derived auxiliary losses stashed in the state pytree
        (``'_aux_loss'`` — the MoE router's load-balancing term). Pad rows
        would contaminate those even with the loss fully masked. Call on a
        BUILT model: lazily-materialized children (keras wrappers) only
        appear in ``walk()`` after build."""
        from ..nn.normalization import BatchNormalization

        if any(isinstance(m, BatchNormalization) for m in self.model.walk()):
            return True

        def has_aux(s) -> bool:
            if isinstance(s, dict):
                return any(
                    k == "_aux_loss" or has_aux(v) for k, v in s.items()
                )
            if isinstance(s, (list, tuple)):
                return any(has_aux(v) for v in s)
            return False

        return has_aux(self.model.get_state())

    def _ragged_seam_policy(self) -> str:
        """How the prefetch seam treats a train batch shorter than the step
        shape: ``'pad'`` (pad + mask via ``nvalid``; needs a mask-capable
        criterion), ``'drop'`` (reference semantics), or ``'pass'`` (hand it
        through untouched; the optimizer's own step handles shapes —
        DistriOptimizer, whose SPMD steps take no ``nvalid``)."""
        return "pad" if self._mask_ragged else "drop"

    # ------------------------------------------------------------ shared bits
    def _clip_grads(self, grads):
        if self._grad_clip_const is not None:
            lo, hi = self._grad_clip_const
            grads = jax.tree_util.tree_map(lambda g: jnp.clip(g, lo, hi), grads)
        if self._grad_clip_norm is not None:
            leaves = jax.tree_util.tree_leaves(grads)
            norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
            scale = jnp.minimum(1.0, self._grad_clip_norm / (norm + 1e-12))
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        return grads

    def _loss_fn(self, params, state, x, t, rng):
        """The one loss every step builder differentiates. Its two parts
        carry step-part scopes (``model_apply``, ``criterion``: the loss, the
        regulariser and the auxiliary terms), so a device op of the step is
        owned by a part, forward under the bare name and backward under
        ``transpose(jvp(...))``, which JAX writes itself."""
        with jax.named_scope("model_apply"):
            y, new_state = self.model.apply(
                params, state, x, training=True, rng=rng)
        with jax.named_scope("criterion"):
            loss, counted = self.criterion.counted(y, t)
            if counted:  # the criterion's parts, into the model's counter slots
                new_state = self.model.with_counters(new_state, counted)
            reg = self.model.regularization_loss_tree(params)
            aux = self.model.auxiliary_loss_tree(new_state)
            return loss + reg + aux, new_state

    def _masked_loss_fn(self, params, state, x, t, rng, nvalid):
        """``_loss_fn`` over the first ``nvalid`` rows of a batch padded to the
        step's static shape: the pad rows are masked out of the loss EXACTLY
        via the criterion's per-sample decomposition, so the ragged final
        batch of an epoch reuses the full batch's one compiled executable.
        ``nvalid`` is a traced scalar — shape-independent, never a retrace."""
        with jax.named_scope("model_apply"):
            y, new_state = self.model.apply(
                params, state, x, training=True, rng=rng)
        with jax.named_scope("criterion"):
            return self._masked_criterion(params, new_state, y, t, nvalid)

    def _masked_criterion(self, params, new_state, y, t, nvalid):
        """The masked loss of ``_masked_loss_fn`` from the model's output on."""
        pair = self.criterion.unreduced(y, t)
        if pair is None:
            raise TypeError(
                f"{type(self.criterion).__name__}.unreduced() returned None "
                "at trace time although supports_unreduced() claimed a "
                "row-wise form; override supports_unreduced() to return "
                "False for this configuration so the ragged seam falls back "
                "to drop semantics"
            )
        per, denom = pair
        # batch axis from the model OUTPUT — input leaves are unreliable (a
        # Table's sparse columns lead with nnz, not batch rows)
        b = jax.tree_util.tree_leaves(y)[0].shape[0]
        row = (jnp.arange(b) < nvalid).astype(per.dtype)
        if per.ndim == 1 and per.shape[0] != b and per.shape[0] % b == 0:
            # flattened (batch*positions,) rows, e.g. ClassNLL over sequences
            mask = jnp.repeat(row, per.shape[0] // b)
        else:
            mask = row.reshape((b,) + (1,) * (per.ndim - 1))
        num = jnp.sum(per * mask)
        if getattr(self.criterion, "size_average", True):
            loss = num / jnp.maximum(jnp.sum(denom * mask), 1e-8)
        else:
            loss = num
        reg = self.model.regularization_loss_tree(params)
        aux = self.model.auxiliary_loss_tree(new_state)
        return loss + reg + aux, new_state

    def _first_batch_input(self):
        """Peek the first training batch (datasets return fresh generators, so
        nothing is consumed) to build the model lazily from its spec."""
        first = next(iter(self.dataset.data(train=True)), None)
        if first is None:
            raise ValueError(
                f"dataset yields no full training batch: size={self.dataset.size()} "
                "is smaller than the batch size (ragged train batches are dropped)"
            )
        return _to_device_tree(first.get_input())

    def _make_standard_step(self, method):
        """jit one (forward, loss, backward, update) step — the whole hot loop.

        ``donate_argnums=(0, 1, 2)`` (params, model_state, slots) lets XLA
        alias the update into the input buffers: weights change IN PLACE
        instead of allocating a second params+slots footprint and copying —
        the zero-copy half of the hot-path contract (docs/performance.md).
        Driver-side state (``box`` in ``_run_with_step``, checkpoints,
        summaries, validation) is rebound to the step's OUTPUT arrays before
        the next dispatch, so nothing ever reads a donated buffer.

        Every step also takes ``nvalid`` (traced scalar, real rows in a
        batch the prefetch seam padded to the static step shape); with a
        mask-capable criterion the loss covers exactly those rows, so a
        ragged final batch costs zero recompiles AND still trains."""
        n_micro = getattr(self, "_micro_batches", 1)
        donate = (0, 1, 2) if self.donate else ()
        # resolve the seam policy HERE, on the built model (every caller
        # builds before constructing the step); _prefetch_batches reads the
        # result when the epoch loop starts
        use_mask = self._mask_ragged = (
            self._criterion_maskable and not self._has_batch_coupled_state()
        )
        hm = self.health
        # GSPMD/hybrid mesh localization: HybridParallelOptimizer sets
        # (n_data_shards,) before building the step, and the health matrix
        # gains per-data-shard non-finite input/target counts so a poisoned
        # record is blamed on its mesh coordinate (None on the local path)
        mesh_shards = getattr(self, "_health_mesh_shards", None)
        counter_names = tuple(sorted(
            self.model.counters_tree(self.model.get_state())))

        def finish(grads, old_params, new_params, new_ms, new_slots, loss,
                   x=None, t=None):
            """Common step tail: with health attached, one extra fixed-shape
            f32 output of in-graph statistics; detached, the exact pre-health
            4-tuple (bit-identical program). A model that keeps counters in
            its state adds their vector as the last output."""
            outs = (new_params, new_ms, new_slots, loss)
            if hm is not None:
                stats = hm.tree_stats(grads, old_params, new_params, new_ms)
                if mesh_shards is not None and x is not None:
                    stats["shards"] = hm.mesh_shard_stats(x, t, mesh_shards)
                outs = outs + (stats,)
            if counter_names:
                # the model's '_counters' (nn.RoutedExperts), reduced over its
                # modules: one small vector beside the loss, so the values
                # outlive the donation of the state that carries them
                counters = self.model.counters_tree(new_ms)
                outs = outs + (jnp.stack([
                    jnp.asarray(counters[k], jnp.float32)
                    for k in counter_names]),)
            return outs

        def loss_fn(params, ms, x, t, rng, nvalid):
            if use_mask:
                return self._masked_loss_fn(params, ms, x, t, rng, nvalid)
            return self._loss_fn(params, ms, x, t, rng)

        # optimize()'s driver rebinds params/ms/slots to the step outputs
        # every iteration — no reference to a donated buffer survives
        @partial(jax.jit, donate_argnums=donate)
        @step_program_name
        def train_step(params, model_state, slots, x, t, nvalid, lr, step, rng):
            (loss, new_model_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params, model_state, x, t, rng, nvalid)
            with jax.named_scope("optim_update"):
                grads = self._clip_grads(grads)
                new_params, new_slots = method.update(
                    grads, params, slots, lr, step)
            return finish(grads, params, new_params, new_model_state,
                          new_slots, loss, x, t)

        if n_micro == 1:
            self._counter_step = (train_step, counter_names)
            return train_step

        def _split(a):
            if a.shape[0] % n_micro:
                raise ValueError(
                    f"batch size {a.shape[0]} not divisible by "
                    f"micro batch count {n_micro}")
            return a.reshape((n_micro, a.shape[0] // n_micro) + a.shape[1:])

        @partial(jax.jit, donate_argnums=donate)
        @step_program_name
        def micro_step(params, model_state, slots, x, t, nvalid, lr, step, rng):
            xs = jax.tree_util.tree_map(_split, x)
            ts = jax.tree_util.tree_map(_split, t)
            rngs = jax.random.split(rng, n_micro)

            if not use_mask:
                def body(carry, sl):
                    g_acc, ms = carry
                    xm, tm, rm = sl
                    (loss_m, ms2), g = jax.value_and_grad(
                        self._loss_fn, has_aux=True
                    )(params, ms, xm, tm, rm)
                    g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                    return (g_acc, ms2), loss_m

                zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
                (g_sum, new_model_state), losses = jax.lax.scan(
                    body, (zeros, model_state), (xs, ts, rngs))
                grads = jax.tree_util.tree_map(lambda g: g / n_micro, g_sum)
                with jax.named_scope("optim_update"):
                    grads = self._clip_grads(grads)
                    new_params, new_slots = method.update(
                        grads, params, slots, lr, step)
                return finish(grads, params, new_params, new_model_state,
                              new_slots, jnp.mean(losses), x, t)

            # masked variant: microbatch m holds clip(nvalid - m*mb, 0, mb)
            # real rows (pads sit at the batch tail), so per-micro masked
            # losses/grads are combined weighted by their real-row counts —
            # equal to the full-batch masked mean for uniform-denominator
            # criterions, and the mean of micro means otherwise.
            b = jax.tree_util.tree_leaves(x)[0].shape[0]
            mb = b // n_micro

            def body(carry, sl):
                g_acc, l_acc, v_acc, ms = carry
                xm, tm, rm, i = sl
                v = jnp.clip(nvalid - i * mb, 0.0, 1.0 * mb)
                (loss_m, ms2), g = jax.value_and_grad(
                    self._masked_loss_fn, has_aux=True
                )(params, ms, xm, tm, rm, v)
                g_acc = jax.tree_util.tree_map(
                    lambda a, gm: a + gm * v, g_acc, g)
                return (g_acc, l_acc + loss_m * v, v_acc + v, ms2), loss_m

            zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
            (g_sum, l_sum, v_sum, new_model_state), _ = jax.lax.scan(
                body, (zeros, 0.0, 0.0, model_state),
                (xs, ts, rngs, jnp.arange(n_micro, dtype=jnp.float32)))
            v_sum = jnp.maximum(v_sum, 1.0)
            grads = jax.tree_util.tree_map(lambda g: g / v_sum, g_sum)
            with jax.named_scope("optim_update"):
                grads = self._clip_grads(grads)
                new_params, new_slots = method.update(
                    grads, params, slots, lr, step)
            return finish(grads, params, new_params, new_model_state,
                          new_slots, l_sum / v_sum, x, t)

        self._counter_step = (micro_step, counter_names)
        return micro_step

    def _cached_standard_step(self, method):
        """The jitted step for (method, micro-batch config) — REUSED across
        retry/resume attempts, so a resume re-dispatches into the
        already-compiled executable instead of paying a second trace+compile
        (the PR 2 "exactly 1 compile" invariant holds through a retry)."""
        if self.health is not None:
            # refresh the monitor's row layout for THIS model/state structure
            # — on cache HITS too: the structure may have changed since the
            # step was cached (e.g. profile_optimizer caches the step before
            # _install_health seeds the activation entries), and the jitted
            # fn retraces per input structure while the bindings would not
            self.health.bind_tree(self.model.get_parameters())
            self.health.bind_acts(self.model.get_state())
        cached = self._step_cache
        n_micro = getattr(self, "_micro_batches", 1)
        if (
            cached is not None
            and cached[0] is method
            and cached[1] == n_micro
            and cached[2] is self.health  # program shape differs with health
        ):
            return cached[3]
        step = self._make_standard_step(method)
        self._step_cache = (method, n_micro, self.health, step)
        return step

    def _run_with_step(self, train_step, params, model_state, slots,
                       place_batch=None, codec=None,
                       entry_params=None, entry_slots=None,
                       extra=None, bind_box=None) -> AbstractModule:
        """Drive the epoch loop over a jitted step with the standard signature
        ``(params, model_state, slots[, extra], x, t, nvalid, lr, step, rng)``
        — the one run loop of every optimizer (local, Distri, hybrid,
        pipeline and expert).

        ``place_batch(x, t)`` optionally commits the batch to a sharding before
        dispatch (the hybrid pjit batch sharding, the Distri ``P(axis)``
        commit); it runs inside the prefetch thread so the placement overlaps
        compute.

        With ``codec`` (a FlatParameter), ``params``/``slots`` are the FLAT
        master vectors: the hot loop carries them untouched, and the per-leaf
        tree is materialized (one jitted unflatten) only at the cold seams
        that genuinely need it — checkpoints, validation, parameter
        histograms, and the final model sync. ``entry_params`` is the tree
        the entry snapshot stores (the restore contract is tree-shaped);
        ``entry_slots`` the f32 slot representation to snapshot when the run
        carries low-precision-encoded slots. ``extra`` is an additional
        carried+donated step state (the comms error-feedback residual),
        threaded through the step right after the slots. ``bind_box(box)``
        is called once with the carried state before the first dispatch
        (the elastic fleet writer slices the live master vector from it)."""
        self._capture_entry_snapshot(
            entry_params if codec is not None else params, model_state,
            entry_slots if entry_slots is not None else slots,
        )
        model, state = self.model, self.optim_method.state
        box = {"params": params, "model_state": model_state, "slots": slots,
               "extra": extra}
        has_extra = extra is not None
        if bind_box is not None:
            bind_box(box)
        self._place_batch = place_batch
        self._jit_step = train_step  # compile-count introspection (tests)

        hm = self.health
        # names of the counters this step hands out as its last output: set
        # by _make_standard_step for the step it built, none for any other
        made, names = self._counter_step
        counter_names = self._counter_names = names if made is train_step else ()

        # the real (unpadded) rows of each batch as a device scalar, put once
        # for each count a fit sees (the full batch, a ragged tail): a fresh
        # put every step is a host→device transfer on the driver thread,
        # ~2 ms a step on a four-chip v5e host beside the worker's batch
        # transfers
        nvalid_of = {}

        def run_iteration(batch, lr: float):
            # the three spans below split the driver's `dispatch` span: host
            # work that delays the enqueue, the enqueue, and what follows it
            # (the carried state rebound, the walk of the module tree)
            with obs_span("step_args"):
                n = batch.size()
                nvalid = nvalid_of.get(n)
                if nvalid is None:
                    nvalid = nvalid_of[n] = jnp.asarray(n, jnp.float32)
                args = (box["params"], box["model_state"], box["slots"])
                if has_extra:
                    args = args + (box["extra"],)
                args = args + (
                    # device arrays: the prefetch seam converted and placed them
                    batch.get_input(),
                    batch.get_target(),
                    nvalid,
                    jnp.asarray(lr, jnp.float32),
                    jnp.asarray(state["neval"]),
                    RandomGenerator.next_key(),
                )
                self._capture_step_specs(train_step, args)
            # box rebinds to the step OUTPUTS below, so with donation on,
            # nothing downstream (checkpoint/summary/validation readers go
            # through the box getters) ever touches the donated input buffers
            with obs_span("step_call"):
                outs = train_step(*args)
            with obs_span("model_sync"):
                # the last references to the step's inputs go here and in
                # the rebinding below: freeing a few hundred array objects
                # is most of a millisecond, and it belongs to a span so that
                # the three close `dispatch`
                del args
                if has_extra:
                    (box["params"], box["model_state"], box["slots"],
                     box["extra"], loss) = outs[:5]
                    tail = 5
                else:
                    (box["params"], box["model_state"], box["slots"],
                     loss) = outs[:4]
                    tail = 4
                if codec is None:
                    # flat mode deliberately skips this: re-materializing the
                    # tree every step is exactly the per-step copy the flat
                    # layout exists to kill (the model syncs at the cold seams)
                    model.set_parameters(box["params"])
                model.set_state(box["model_state"])
            counters = outs[-1] if counter_names else None
            if hm is not None:  # health stats ride the same one-step-late pull
                return loss, outs[tail], counters
            if counters is not None:
                return loss, None, counters
            return loss  # device array — _drive_loop pulls it one step later

        if codec is None:
            get_params = lambda: box["params"]  # noqa: E731
            get_slots = lambda: box["slots"]  # noqa: E731
        else:
            get_params, get_slots = self._flat_state_thunks(codec, box)
        self._drive_loop(
            run_iteration,
            get_params,
            get_slots,
            lambda: box["model_state"],
        )
        model.set_parameters(get_params())
        model.set_state(box["model_state"])
        return model

    def _prefetch_batches(self, it, depth: int = 2, qsize=None, close=None,
                          turnover=None):
        """Host→device double-buffering (SURVEY.md §3.1 hot-loop notes).

        A background thread converts + ``device_put``s the next ``depth`` batches
        while the current step runs, so the transfer overlaps compute instead of
        serializing in front of each dispatch. The reference gets the same
        overlap from Spark's pipelined partition iterators.

        This is also the ragged-batch seam: the first batch fixes the step's
        static row count, and any later SHORT batch (a transformer chain's
        epoch tail) is padded back to it on the host — masked out of the loss
        via ``nvalid`` when the criterion supports it, dropped (reference
        semantics) when it doesn't. Either way the jitted step sees ONE shape
        per fit and compiles exactly once.

        Starvation observability, one wait on each side of the ring. The
        WORKER's wait is the ``dataset_next`` span around ``next(src)``: the
        dataset layer's work for one batch, carried to the step record as
        ``input_wait_s`` (the same pair of clock reads). It says how long a
        batch took to make, not whether the step waited for it. The
        DRIVER's wait is the ``ring_wait`` span around ``ring.get()``: time
        the step itself waited for data, zero while the worker stays ahead.
        The worker also samples the pipeline's staging depth through
        ``qsize`` (a ``DataPipeline`` stream's ring gauge) and counts the
        host bytes it hands to the placement seam (``h2d_bytes``); the
        rest of its period is its block in ``ring.put``, which needs no
        span. ``turnover`` is ``_drive_epochs``'s holder of the open
        ``epoch_turnover`` span: it opens here when the ring reports the
        epoch's end and closes here before the next epoch's first
        ``ring.get()``, so that wait is ``ring_wait`` and not the boundary's.

        Who owns a host batch buffer, when. A batch that carries a
        ``host_lease`` (``LocalArrayDataSet``'s fast path; see
        ``dataset.HostBuffers``) was gathered into a buffer its dataset
        would take back. From ``next(src)`` on the buffer is the worker's:
        it goes through the placement seam, then waits in a local queue
        beside the device leaves made from it. At the top of each turn,
        before ``next(src)``, the worker hands back, oldest first, every
        buffer that ``_host_buffer_free`` finds free: each leaf ready (the
        host→device copy is done) and none living inside the buffer (no
        alias). From the hand-back on it is the dataset's, which gathers the
        next batch into it. The worker never waits for this: a buffer whose
        copy is still running stays a turn, and the dataset allocates fresh
        meanwhile (a miss: ``host_buf_reused`` 0 in that batch's step
        record). An aliased buffer is never handed back and lives as long as
        its device array, as every buffer did before. A batch that was
        padded (the pad is a copy) or dropped is free at once. On early exit
        the queue is dropped with the thread; ``_drive_loop`` empties the
        dataset's list when the run ends. A batch without a lease takes none
        of these steps.

        Shutdown is event-aware (``StagingRing``): when the consumer
        abandons the epoch (trigger, exception, retry), ``close()`` wakes a
        blocked worker immediately and drops the buffered device batches, so
        nothing stays pinned for a poll tick."""
        import threading

        from ..dataset.pipeline import RING_CLOSED, StagingRing

        ring = StagingRing(depth)
        END = object()

        place = getattr(self, "_place_batch", None)
        policy = self._ragged_seam_policy()
        # the worker's spans must land in THIS run's collector (span sinks
        # are thread-bound so concurrent runs cannot cross-steal samples)
        span_collector = obs_trace.current_collector()

        # (device leaves, lease) of the batches whose host buffer may still
        # be read by its copy, oldest first; the worker's own. Bounded by
        # what can be in flight (the ring, the batch in put, the one being
        # placed): an entry pushed out is a buffer nobody hands back
        inflight = collections.deque(maxlen=depth + 2)

        def worker():
            obs_trace.bind_collector(span_collector)
            try:
                src = iter(it)
                while True:
                    while inflight:
                        leaves, held = inflight[0]
                        free = _host_buffer_free(leaves, held.buffer)
                        if free is None:
                            break  # still copying: it waits a turn
                        inflight.popleft()
                        if free:
                            held.hand_back()
                    with obs_span("dataset_next") as waited:
                        batch = next(src, END)
                    if batch is END:
                        break
                    qdepth = qsize() if qsize is not None else None
                    if ring.closed:
                        return
                    # causal context minted by the upstream pipeline for
                    # this chunk (None off non-traced iterators): bound
                    # below so pad/place spans chain onto its transform
                    # span, then carried on the device batch to the driver
                    ctx = getattr(src, "last_context", None)
                    if ctx is None:
                        ctx = getattr(it, "last_context", None)
                    prev_ctx = obs_trace.bind_context(ctx)
                    lease = getattr(batch, "host_lease", None)
                    if lease is not None:
                        self._host_buffers = lease.pool
                    try:
                        n = batch.size()
                        if policy == "pass":
                            pass  # optimizer's step owns shape handling
                        elif self._step_rows is None:
                            self._step_rows = n
                        elif n < self._step_rows:  # epoch tail shorter than step
                            with obs_span("pad_mask"):
                                padded = (
                                    pad_minibatch(batch, self._step_rows)
                                    if policy == "pad"
                                    else None
                                )
                            if lease is not None:
                                lease.hand_back()  # padded copy or dropped
                            if padded is None:
                                if not getattr(self, "_warned_ragged_drop", False):
                                    self._warned_ragged_drop = True
                                    log.warning(
                                        "dropping ragged %d-row batch (step shape "
                                        "is %d rows and it cannot be pad-masked: "
                                        "criterion without a per-sample "
                                        "decomposition, batch-coupled model "
                                        "state such as BatchNorm/MoE-aux, or "
                                        "non-dense leaves)",
                                        n, self._step_rows,
                                    )
                                continue
                            batch, n = padded  # padded rows, real count n
                        # counted only on an attached run, like the spans
                        h2d = (
                            _host_nbytes((batch.get_input(),
                                          batch.get_target()))
                            if span_collector is not None else None
                        )
                        reused = (
                            int(lease.reused)
                            if lease is not None
                            and span_collector is not None else None
                        )
                        with obs_span("prefetch"):
                            if place is not None:
                                # placement seam owns convert + sharding commit
                                # in ONE host→device hop (hybrid pjit batch
                                # sharding, DistriOptimizer async placement) —
                                # running here, it overlaps the current step's
                                # compute instead of serializing in front of the
                                # next dispatch
                                x, t = place(batch.get_input(),
                                             batch.get_target())
                            else:
                                x = _to_device_tree(batch.get_input())
                                t = _to_device_tree(batch.get_target())
                                x, t = jax.device_put((x, t))
                    finally:
                        obs_trace.bind_context(prev_ctx)
                    if lease is not None and lease.buffer is not None:
                        inflight.append(
                            (jax.tree_util.tree_leaves((x, t)), lease))
                    if not ring.put(_DeviceBatch(x, t, n, waited.s, qdepth,
                                                 h2d, reused, trace=ctx)):
                        return
                ring.put(END)
            except BaseException as e:  # propagate into the training loop
                ring.put(e)

        t = threading.Thread(target=worker, daemon=True)
        self._prefetch_thread = t  # shutdown-promptness introspection (tests)
        t.start()
        if turnover is not None:
            turnover.close()  # the boundary ends where this epoch's wait begins
        try:
            while True:
                with obs_span("ring_wait"):
                    item = ring.get()
                if item is END:
                    if turnover is not None:
                        # the epoch ran out: what follows, from this
                        # generator's own teardown to the next epoch's first
                        # get, is the boundary's cost
                        turnover.enter_context(obs_span("epoch_turnover"))
                    return
                if item is RING_CLOSED:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            # early exit (max_iteration trigger, exception, retry attempt):
            # close the ring — a blocked worker put wakes NOW (no poll tick)
            # and the buffered device batches free immediately
            ring.close()
            # tear the upstream pipeline's worker pool down too. `close` is
            # the ORIGINAL stream's close when the caller wrapped `it` (the
            # resume path's islice exposes none — without this the pipeline
            # pool would stay pinned on an abandoned resumed epoch). A
            # DataPipeline stream closes its rings first (thread-safe); a
            # PLAIN generator mid-next() on the worker thread raises
            # ValueError — the ring close above already unblocked the
            # worker, which lets the generator finish on its own.
            close_fn = close if close is not None else getattr(it, "close", None)
            if close_fn is not None:
                try:
                    close_fn()
                except ValueError:
                    pass

    def _drive_loop(self, run_iteration, get_params, get_slots, get_model_state):
        """Shared epoch/iteration driver (used by Local and Distri optimizers).

        ``run_iteration(batch, lr) -> loss (device array)`` dispatches one step and
        keeps ``self.model`` in sync; epoch bookkeeping keys off train-iterator
        exhaustion (ragged tails are dropped by the dataset).

        The loss is pulled to host ONE STEP LATE: step i's scalar is read after
        step i+1 has been dispatched, so the device always has a step queued and
        the host-side log never serializes dispatch against compute (round-1
        finding: a per-step ``float(loss)`` was the loop's only real sync and
        blocked the device every iteration). Consequence: ``Trigger.min_loss``
        and the logged loss lag the true step by one iteration.
        """
        state = self.optim_method.state
        # perf_counter for DURATIONS (BDL006): time.time is for timestamps
        t_start = time.perf_counter()
        stop = False
        param_trigger = (
            getattr(self.summary, "trigger_for", lambda _n: None)("Parameters")
            if self.summary is not None
            else None
        )
        from ..utils.serialization import flatten_pytree

        mark = {"t": None}  # host time of the previous loss pull
        tel = self.telemetry
        pol = self._active_policy
        hmon = self.health
        # perf accounting rides the flush seam ONLY with telemetry attached
        # (a detached fit pays nothing, like spans/health)
        pa = self._perf if tel is not None else None

        def flush(rec) -> None:
            """Pull a completed step's loss and emit log line + summaries."""
            (neval, epoch, iter_in_epoch, loss_arr, n, lr, dispatch_s,
             health_arr, input_wait_s, input_qdepth, h2d_bytes,
             host_buf_reused, counters_arr) = rec
            try:
                # one-step-late pull: step i's scalar lands after step i+1 is
                # queued — device-side faults from step i surface HERE. The
                # span holds the sync and nothing else: the host blocked on
                # the device, the reading of "the device sets the pace"
                with obs_span("loss_pull"):
                    loss_f = float(loss_arr)  # lint: disable=BDL005 deliberate delayed host sync
            except Exception as e:
                try:
                    # attribute the fault to the step that PRODUCED the loss;
                    # the live _iter_in_epoch already names the next batch
                    e._bigdl_position = (epoch, iter_in_epoch)
                except (AttributeError, TypeError):
                    pass  # __slots__ exception: the live-position fallback applies
                raise
            if (
                pol is not None
                and pol.divergence_guard
                and not math.isfinite(loss_f)
            ):
                # divergence guard: zero NEW host syncs — the loss is the
                # value the driver already pulls one step late. Params are
                # poisoned from this step on; recovery = rollback to the
                # newest FINITE verified checkpoint (_recover). With health
                # attached, the SAME step's in-graph non-finite counters name
                # the first poisoned layer and whether grads or weights went
                # bad — the rollback record stops being a blind retry.
                layer = source = shard = None
                if hmon is not None and health_arr is not None:
                    snap = hmon.snapshot(health_arr)
                    layer, source = hmon.attribute_nonfinite(snap)
                    shard = hmon.attribute_shard(snap)
                raise DivergenceError(
                    loss_f, neval, position=(epoch, iter_in_epoch),
                    layer=layer, source=source, shard=shard,
                )
            now = time.perf_counter()
            wall = now - mark["t"] if mark["t"] is not None else 0.0
            mark["t"] = now
            if wall:
                self.metrics.add("computing time for each node average", wall)
            throughput = n / max(wall, 1e-9)
            state["loss"] = loss_f
            self._log_iteration(
                {"epoch": epoch, "neval": neval},
                loss_f,
                n,
                time.perf_counter() - t_start,
                throughput,
            )
            with obs_span("summary_flush"):
                if self.summary is not None:
                    self.summary.add_scalar("Loss", loss_f, neval)
                    self.summary.add_scalar("LearningRate", lr, neval)
                    self.summary.add_scalar("Throughput", throughput, neval)
                if tel is not None:
                    if pa is not None:
                        # once per compiled step (identity-keyed): derive the
                        # program cost from the captured specs while the
                        # device executes the step just dispatched — the
                        # join itself is host arithmetic on values already
                        # in hand (zero new syncs)
                        pa.ensure_cost(self._jit_step, self._step_export_info)
                    step_rec = tel.step(
                        path=type(self).__name__,
                        iteration=neval,
                        epoch=epoch,
                        loss=loss_f,
                        lr=lr,
                        records=n,
                        wall_s=wall,
                        records_per_sec=throughput,
                        dispatch_s=dispatch_s,
                        input_wait_s=input_wait_s,
                        input_qdepth=input_qdepth,
                        h2d_bytes=h2d_bytes,
                        host_buf_reused=host_buf_reused,
                        # the same step's outputs as the loss just pulled:
                        # a copy of ready buffers, not a new sync
                        **(dict(zip(
                            self._counter_names,
                            np.asarray(counters_arr).tolist()))  # lint: disable=BDL005 rides the delayed loss sync above
                           if counters_arr is not None else {}),
                        **(pa.step_fields(wall) if pa is not None else {}),
                    )
                    if pa is not None:
                        # window accumulation + PerfMonitor breach check +
                        # bounded capture management, all from the emitted
                        # record's host-side fields
                        for ev in pa.note_step(step_rec):
                            log.warning(
                                "perf regression at iteration %d: %s "
                                "(component=%s)", neval, ev.get("trigger"),
                                ev.get("component"),
                            )
                            tel.warn(path=type(self).__name__, **ev)
                        if pa.should_emit():
                            tel.perf(
                                iteration=neval,
                                epoch=epoch,
                                path=type(self).__name__,
                                **pa.perf_fields(),
                            )
                    if (
                        hmon is not None
                        and health_arr is not None
                        and hmon.should_emit(neval)
                    ):
                        # the stats were computed in-graph by the SAME step
                        # whose loss was just pulled — materializing them
                        # here is a copy of ready buffers, not a new sync;
                        # the stride bounds this host-side cost
                        fields = hmon.record_fields(hmon.snapshot(health_arr))
                        tel.health(
                            iteration=neval,
                            epoch=epoch,
                            path=type(self).__name__,
                            **fields,
                        )
                        guard = hmon.lr_guard_event(fields)
                        if guard is not None:
                            # update_ratio auto-LR guard: advisory only — it
                            # fires while the loss is still finite, BEFORE
                            # the divergence guard's rollback would
                            log.warning(
                                "update/weight ratio %.3g above %.3g for %d "
                                "consecutive health samples (%s) at iteration "
                                "%d — learning rate %g may be too high",
                                guard["ratio"], guard["bound"],
                                guard["consecutive"],
                                guard["layer"] or "global", neval, lr,
                            )
                            tel.warn(
                                iteration=neval,
                                path=type(self).__name__,
                                lr=lr,
                                **guard,
                            )

        import itertools

        if tel is not None:
            if self._jit_step is not self._compiles_fn:
                # fresh jit fn (first run, or a rebuilt step): reset the
                # cache-entry watermark. A REUSED step across a retry keeps
                # it, so a resume that hits the already-compiled executable
                # reports ZERO new compile events.
                self._compiles_seen = 0
                self._compiles_fn = self._jit_step
            from ..utils.compat import CacheDirWatch

            # snapshot the persistent cache before the first dispatch so
            # each observed compile can be classified fresh vs disk-read
            # (the artifact warm-boot proof); one listdir per detected
            # compile, never per step
            self._cache_watch = CacheDirWatch()
            if pa is not None:
                # per-run perf reset: peaks re-resolved, monitor baseline
                # cleared (run 2 must not be judged by run 1's medians);
                # the derived cost survives — it is keyed by step identity
                pa.begin_run(n_devices=self._perf_device_count())
            tel.run_started(
                type(self).__name__,
                warm_start=self._warm_start_bundle,
                # the stream is self-describing: which low-precision policy
                # (comms/master/slot dtypes + error feedback) shaped this run
                low_precision=(
                    self._precision.describe()
                    if self._precision is not None else None
                ),
            )
        watchdog = tel.watchdog if tel is not None else None
        if (
            pol is not None
            and watchdog is not None
            and watchdog is not self._stall_cb_watchdog
        ):
            # the PR 3 watchdog's first consumer: stall callbacks feed the
            # policy, which escalates into a snapshot + controlled restart.
            # The registered forwarder is a STABLE bound method reading
            # _active_policy, so a later optimize() with a different (or
            # fresh legacy-shim) policy keeps receiving escalations; a
            # swapped Telemetry/watchdog re-registers (and deregisters from
            # the old one, which would otherwise pin this optimizer alive).
            if self._stall_cb_watchdog is not None:
                self._stall_cb_watchdog.remove_callback(self._on_watchdog_stall)
            watchdog.add_callback(self._on_watchdog_stall)
            self._stall_cb_watchdog = watchdog
        try:
            # `turnover` holds the open `epoch_turnover` span between one
            # epoch's last ring.get() and the next one's first; leaving the
            # block closes a span the run ended or unwound under (the last
            # epoch's closing lands in the run_end record's spans)
            with contextlib.ExitStack() as turnover:
                self._drive_epochs(run_iteration, get_params, get_slots,
                                   get_model_state, state, stop, mark, flush,
                                   param_trigger, flatten_pytree, itertools,
                                   turnover)
        finally:
            # training may end (trigger, exception, retry) mid-trace-window:
            # an unstopped profiler never flushes and poisons the next start
            profile = getattr(self, "_profile", None)
            if profile is not None and profile.get("on"):
                from ..obs import perf as obs_perf

                obs_perf.stop_capture()
                self._profile = None
            # the run is over (or unwinding into a retry): the dataset's
            # free host buffers go, and leases still out give nothing back
            pool, self._host_buffers = self._host_buffers, None
            if pool is not None:
                pool.clear()
            if pa is not None:
                pa.end_run()  # a breach capture still open flushes here
            if tel is not None:
                tel.run_ended(type(self).__name__,
                              iterations=state.get("neval"))

    def _drive_epochs(self, run_iteration, get_params, get_slots,
                      get_model_state, state, stop, mark, flush,
                      param_trigger, flatten_pytree, itertools, turnover):
        pending = None
        # dataset-cooperative poison skip: a dataset that advertises
        # supports_skip_positions (DataPipeline) receives the policy's
        # quarantine set and never parses/transforms/places those batches;
        # the loop below just advances past the holes. Everything else keeps
        # the legacy consume-and-drop path.
        cooperative = bool(
            getattr(self.dataset, "supports_skip_positions", False)
        )
        while not stop:
            self.dataset.shuffle(state["epoch"])  # epoch-deterministic order
            state["_epoch_done"] = False
            pol0 = self._active_policy
            skip_set = (
                frozenset(pol0.skip_positions)
                if cooperative and pol0 is not None else frozenset()
            )
            if cooperative and pol0 is not None:
                raw = self.dataset.data(train=True, skip_positions=skip_set)
            else:
                raw = self.dataset.data(train=True)
            qsize = getattr(raw, "qsize", None)  # staging-depth gauge
            # captured BEFORE any islice wrap below: the wrapper hides the
            # stream's close(), which the prefetcher needs for teardown
            close = getattr(raw, "close", None)
            skip = self._resume_skip_iters
            if skip:  # resume mid-epoch: same permutation, skip consumed batches
                self._resume_skip_iters = 0
                # _iter_in_epoch counts SLOTS (quarantined holes included);
                # a cooperative dataset never yields the holes, so the
                # number of YIELDED batches to skip shrinks by the holes
                # already behind the resume point
                n_yielded = skip - sum(
                    1 for (e, i) in skip_set
                    if e == state["epoch"] and i < skip
                )
                raw = itertools.islice(raw, max(0, n_yielded), None)
            state["_iter_in_epoch"] = skip
            for batch in self._prefetch_batches(raw, qsize=qsize, close=close,
                                                turnover=turnover):
                pol = self._active_policy
                if cooperative and pol is not None:
                    # quarantined slots were never produced by the dataset:
                    # advance the position accounting past the holes so
                    # resume/replay positions stay aligned with a clean run
                    while (
                        state["epoch"], state.get("_iter_in_epoch", 0)
                    ) in pol.skip_positions:
                        hole = state.get("_iter_in_epoch", 0)
                        log.warning(
                            "skipping batch at poisoned data position "
                            "(epoch %d, batch %d) — dataset-cooperative: "
                            "never parsed/transformed/placed",
                            state["epoch"], hole,
                        )
                        state["_iter_in_epoch"] = hole + 1
                pos = (state["epoch"], state.get("_iter_in_epoch", 0))
                if pol is not None:
                    if pol.stall_pending():
                        info = pol.take_stall()
                        if self.checkpoint_path is None:
                            # nowhere to restore from — _decide_retry would
                            # re-raise and a slow step would kill the run;
                            # degrade to the pre-policy telemetry-only
                            # watchdog semantics instead
                            log.warning(
                                "stall escalation ignored (no checkpoint "
                                "path to restart from): %s", info,
                            )
                        else:
                            # escalation consumer (the watchdog itself never
                            # kills the run): controlled restart of the step
                            # loop via _recover, restoring the last WRITTEN
                            # checkpoint (or the step-0 entry snapshot).
                            # Deliberately NO fresh checkpoint here: pulling
                            # get_params() host-syncs on the very step that
                            # is stalled — a genuinely hung dispatch would
                            # deadlock the escalation path instead of
                            # restarting it.
                            raise StallEscalation(info)
                    if not cooperative and pos in pol.skip_positions:
                        # deterministic poison-batch skip (legacy datasets):
                        # this (epoch, batch) position failed twice —
                        # consume the batch, never dispatch it
                        log.warning(
                            "skipping batch at poisoned data position "
                            "(epoch %d, batch %d)", pos[0], pos[1],
                        )
                        state["_iter_in_epoch"] = pos[1] + 1
                        continue
                guard = self._preemption_guard
                if guard is not None and guard.pending() is not None:
                    self._handle_preemption(state, get_params, get_slots)
                el = self._elastic
                if el is not None and el.poll():
                    # a host's heartbeat went stale: coordinated emergency
                    # checkpoint at THIS consistent step boundary, then
                    # reshard onto the survivors (ElasticRemesh, caught in
                    # optimize())
                    self._handle_host_lost(state, get_params, get_slots)
                lr = self.optim_method.get_learning_rate() * float(
                    state.get("_lr_scale", 1.0)  # divergence LR backoff
                )
                if mark["t"] is None:
                    mark["t"] = time.perf_counter()
                profile = getattr(self, "_profile", None)
                if profile is not None:
                    # captures route through the obs/perf sanctioned seam
                    # (BDL016) — which also serializes this window against
                    # a PerfMonitor breach capture holding the profiler
                    from ..obs import perf as obs_perf

                    if state["neval"] >= profile["start"] + profile["len"]:
                        if profile.get("on"):
                            obs_perf.stop_capture()
                        self._profile = None  # window over (started or not)
                    elif (not profile.get("on")
                          and state["neval"] >= profile["start"]):
                        # may refuse while another capture holds the
                        # profiler; retried next step inside the window
                        profile["on"] = obs_perf.start_capture(profile["dir"])
                # step boundaries for profiler traces; the span is the
                # dispatch's one clock (async dispatch returns fast UNLESS
                # this call compiled) and its chaos seam
                with obs_trace.step_annotation(state["neval"]):
                    with obs_span("dispatch") as dispatched:
                        res = run_iteration(batch, lr)  # dispatch; no sync
                # with health attached, run_iteration also hands back the
                # step's in-graph stats pytree, pulled at the same
                # one-step-late flush as the loss
                # a model that keeps counters (nn.RoutedExperts) adds their
                # vector, pulled at that flush too
                loss_arr, health_arr, counters_arr = (
                    (res + (None,))[:3] if isinstance(res, tuple)
                    else (res, None, None)
                )
                dispatch_s = dispatched.s  # None on a detached run
                if self.telemetry is not None:
                    # close the chunk's causal chain: transform (pipeline
                    # worker) → place (prefetch worker) → dispatch (driver),
                    # carried here on the device batch (BDL022 seam)
                    batch_ctx = getattr(batch, "trace", None)
                    if batch_ctx is not None and batch_ctx.sampled:
                        obs_trace.emit_span(
                            "dispatch", dispatch_s, batch_ctx.child(),
                            iteration=state["neval"],
                        )
                    self._observe_compiles(state["neval"], dispatch_s)
                prev, pending = pending, (
                    state["neval"],
                    state["epoch"],
                    state.get("_iter_in_epoch", 0),  # this batch's position
                    loss_arr,
                    batch.size(),
                    lr,
                    dispatch_s,
                    health_arr,
                    getattr(batch, "input_wait_s", None),
                    getattr(batch, "input_qdepth", None),
                    getattr(batch, "h2d_bytes", None),
                    getattr(batch, "host_buf_reused", None),
                    counters_arr,
                )
                if prev is not None:
                    flush(prev)  # overlaps with the step just dispatched
                state["learningrate"] = lr
                if self.summary is not None and param_trigger is not None and param_trigger(state):
                    for pname, arr in flatten_pytree(get_params()).items():
                        self.summary.add_histogram(pname, arr, state["neval"])
                state["neval"] += 1
                state["_iter_in_epoch"] = state.get("_iter_in_epoch", 0) + 1
                self._run_validation(get_params, get_model_state)
                self._maybe_checkpoint(state, get_params, get_slots)
                if self.end_when(state):
                    stop = True
                    break
            if pending is not None:
                flush(pending)
                pending = None
            if not stop:
                state["_iter_in_epoch"] = 0
                state["epoch"] += 1
                state["_epoch_done"] = True
                self._run_validation(get_params, get_model_state)
                self._maybe_checkpoint(state, get_params, get_slots)
                if self.end_when(state):
                    stop = True
                state["_epoch_done"] = False
                el = self._elastic
                if el is not None and not stop:
                    joined = el.rejoin_ready()
                    if joined:
                        # epoch-boundary re-expansion back to the full mesh
                        self._handle_rejoin(
                            state, get_params, get_slots, joined
                        )

    def _log_iteration(self, state, loss, records, wall, throughput):
        log.info(
            "[Epoch %d][Iteration %d][Wall %.3fs] loss is %.6f, throughput is %.1f records/s",
            state["epoch"],
            state["neval"],
            wall,
            loss,
            throughput,
        )

    def _observe_compiles(self, iteration: int, dispatch_s: float) -> None:
        from ..obs.telemetry import observe_jit_compiles

        self._compiles_seen = observe_jit_compiles(
            self._jit_step, self._compiles_seen, self.telemetry,
            iteration=iteration, seconds=dispatch_s,
            path=type(self).__name__, cache_watch=self._cache_watch,
        )

    def _maybe_checkpoint(self, state, get_params, get_slots) -> None:
        """``get_params``/``get_slots`` are THUNKS, evaluated only when the
        trigger fires: on the flat (ZeRO-1) master-state path, materializing
        the tree view costs a params-sized copy, which must never ride every
        step."""
        if self.checkpoint_path is None or self.checkpoint_trigger is None:
            return
        if self.checkpoint_trigger(state):
            self._write_checkpoint(state, get_params(), get_slots())

    def _write_checkpoint(self, state, params, slots) -> None:
        """One verified (manifest + checksums) checkpoint at the current
        step — shared by the periodic trigger, the preemption handler, the
        stall-escalation snapshot and the elastic coordination point. With
        an elastic fleet writer registered (flat/ZeRO-1 step builder), the
        save routes onto the per-host-sharded fleet format instead — the
        writer slices the live flat master directly, so the tree
        ``params``/``slots`` views passed here are ignored on that path."""
        writer = self._fleet_writer
        if writer is not None:
            with obs_span("checkpoint"):
                manifest = writer(state)
        else:
            from ..utils.serialization import save_checkpoint

            with obs_span("checkpoint"):
                manifest = save_checkpoint(
                    self.checkpoint_path,
                    step=state["neval"],
                    params=params,
                    optim_slots=slots,
                    optim_state=dict(state),
                    model_state=self.model.get_state(),
                    keep_last=self.checkpoint_keep_last,
                )
        if manifest.get("finite") and self._entry_snapshot is not None:
            # a FINITE verified checkpoint now exists on disk, so every
            # restore path (require_finite included) resolves there — free
            # the full host copy of params+slots the snapshot was holding
            self._entry_snapshot = None

    def _on_watchdog_stall(self, info: Dict) -> None:
        pol = self._active_policy
        if pol is not None:
            pol.note_stall(info)

    def _handle_preemption(self, state, get_params, get_slots) -> None:
        """A caught preemption signal is pending: write the emergency
        checkpoint at this (consistent) step boundary, emit the
        ``preempt_checkpoint`` record, and leave with a clean
        :class:`TrainingPreempted` — never retried by the policy."""
        signum = int(self._preemption_guard.pending())
        step = int(state.get("neval", 0))
        ckpt = None
        if self.checkpoint_path is not None:
            self._write_checkpoint(state, get_params(), get_slots())
            ckpt = self.checkpoint_path
        else:
            log.warning(
                "preempted by signal %d with no checkpoint path configured; "
                "run state is lost", signum,
            )
        if self.telemetry is not None:
            self.telemetry.preempt_event(
                signal=signum, step=step, checkpoint_dir=ckpt,
                path=type(self).__name__,
            )
        exc = TrainingPreempted(signum, step=step, checkpoint_dir=ckpt)
        # the emergency checkpoint is down; now freeze the forensics too —
        # a preempted host's bundle is how the operator learns what the
        # fleet was doing when the SIGTERM landed
        self._dump_postmortem_for(exc, "preempted")
        raise exc

    # --------------------------------------------------------- elastic fleet
    def _training_mesh(self):
        """The mesh this fit runs on: the elastic coordinator's view over
        the ACTIVE fleet (survivors' contiguous device blocks) when elastic
        training is attached, the full Engine mesh otherwise."""
        from ..utils.engine import Engine

        mesh = Engine.mesh()
        el = self._elastic
        if el is not None:
            return el.mesh(mesh)
        return mesh

    def _apply_reader_slice(self) -> None:
        """Per-host input slicing: under REAL multi-process execution
        (``Engine.init_distributed``) each process reads only its
        ``shard(process_index, process_count)`` slice of the stream; an
        elastic remesh recomputes the slice as rank-among-survivors. Always
        re-shards from the ORIGINAL dataset, never a previous slice. A
        single-controller run (including simulated fleets, where the driver
        feeds the whole mesh) is a no-op."""
        from ..utils.engine import Engine

        el = self._elastic
        sl = el.reader_slice() if el is not None else None
        if sl is None:
            sl = Engine.process_slice()
        if sl is None:
            return
        index, count = int(sl[0]), int(sl[1])
        if count <= 1:
            return
        base = self._dataset_base
        if base is None:
            base = self._dataset_base = self.dataset
        if not hasattr(base, "shard"):
            log.warning(
                "multi-process fit (process %d of %d) but %s has no "
                "shard(index, count); every process will read the FULL "
                "stream", index, count, type(base).__name__,
            )
            return
        self.dataset = base.shard(index, count)
        log.info(
            "reader slice: process rank %d of %d active (dataset sharded)",
            index, count,
        )

    def _handle_host_lost(self, state, get_params, get_slots) -> None:
        """A host's heartbeat went stale: claim the shrink, coordinate
        (claims the next fleet generation — chaos seam ``coordinate``),
        write the emergency fleet checkpoint at THIS consistent step
        boundary, and raise the internal :class:`ElasticRemesh` signal for
        ``optimize()`` to apply. Viability is checked AFTER the checkpoint
        lands so an exhausted fleet still leaves a resumable run behind."""
        el = self._elastic
        lost = el.take_shrink()
        if not lost:
            return
        step = int(state.get("neval", 0))
        log.warning(
            "elastic: host(s) %s lost — coordinated emergency checkpoint "
            "at step %d, resharding onto the survivors", lost, step,
        )
        el.coordinate(step, kind="shrink")
        self._write_checkpoint(state, get_params(), get_slots())
        el.check_viable(lost)
        raise ElasticRemesh("shrink", lost, step=step)

    def _handle_rejoin(self, state, get_params, get_slots, joined) -> None:
        """Epoch-boundary re-expansion: the returned host re-registered via
        its heartbeat file; checkpoint the CURRENT (shrunk-mesh) state under
        a fresh fleet generation so every process — the rejoiner included —
        restores the same step, then signal the remesh."""
        el = self._elastic
        step = int(state.get("neval", 0))
        log.warning(
            "elastic: host(s) %s re-registered — re-expanding the mesh at "
            "the epoch boundary (step %d)", joined, step,
        )
        el.coordinate(step, kind="rejoin")
        self._write_checkpoint(state, get_params(), get_slots())
        raise ElasticRemesh("rejoin", joined, step=step)

    def _apply_remesh(self, remesh: ElasticRemesh) -> None:
        """Re-slice training onto the new mesh configuration: flip the
        coordinator membership (chaos seams ``reshard``/``rejoin``),
        recompute the reader slice, and restore from the coordinated fleet
        checkpoint the raising step boundary just wrote. The survivors'
        re-flatten under the new codec happens when ``_optimize_impl``
        re-enters on the new mesh — one new compile per mesh configuration,
        cached so repeated shrinks/rejoins reuse."""
        el = self._elastic
        shrink = remesh.kind == "shrink"
        seam = "reshard" if shrink else "rejoin"
        t0 = time.perf_counter()
        with obs_span(f"elastic_{seam}"):
            obs_trace.fault_point(seam)
            if shrink:
                el.apply_shrink(remesh.members)
            else:
                el.apply_rejoin(remesh.members)
            self._apply_reader_slice()
            restored = self._resume_from_checkpoint()
        reshard_s = time.perf_counter() - t0
        log.warning(
            "elastic: %s applied — %d active process(es) %s, generation %d, "
            "restored step %s (%.3fs)", seam, el.n_active(), el.active(),
            el.generation, restored, reshard_s,
        )
        if self.telemetry is not None:
            self.telemetry.warn(
                reason="mesh_shrunk" if shrink else "mesh_rejoin",
                path="elastic",
                iteration=remesh.step,
                members=list(remesh.members),
                process_count=el.n_active(),
                processes=el.active(),
                generation=el.generation,
                restored_step=restored,
                reshard_s=round(reshard_s, 6),
                reader_slices={
                    str(k): list(v) for k, v in el.reader_slices().items()
                },
            )

    def _run_validation(self, get_params, get_model_state) -> Optional[Dict[str, ValidationResult]]:
        """``get_params``/``get_model_state`` are THUNKS — evaluated only when
        the trigger fires (the flat path pays a tree materialization)."""
        if (
            self.validation_trigger is None
            or self.validation_dataset is None
            or not self.validation_trigger(self.optim_method.state)
        ):
            return None
        with obs_span("validation"):
            results = validate(
                self.model, get_params(), get_model_state(),
                self.validation_dataset, self.validation_methods,
            )
        for name, res in results.items():
            v, n = res.result()
            log.info("%s is %.6f (n=%d)", name, v, n)
        # score feeds max_score triggers and Plateau schedules
        first = next(iter(results.values()))
        self.optim_method.state["score"] = first.result()[0]
        self.optim_method.state["n_validations"] = (
            self.optim_method.state.get("n_validations", 0) + 1
        )
        if self.val_summary is not None:
            for name, res in results.items():
                self.val_summary.add_scalar(name, res.result()[0], self.optim_method.state["neval"])
        return results


def validate(model, params, model_state, dataset, methods) -> Dict[str, ValidationResult]:
    """Shared eval loop: jitted forward + pure metric counters, merged on host
    (reference: Evaluator / DistriValidator semantics)."""

    # cache the jitted eval on the model — a fresh jit wrapper per call would
    # retrace/recompile the whole eval graph at every validation event
    eval_step = getattr(model, "_jit_eval_step", None)
    if eval_step is None:
        eval_step = jax.jit(
            lambda params, model_state, x: model.apply(
                params, model_state, x, training=False, rng=None
            )[0]
        )
        model._jit_eval_step = eval_step

    totals: Dict[str, ValidationResult] = {}
    expected = None  # first batch fixes the eval executable's static shape
    for batch in dataset.data(train=False):
        n = batch.size()
        if expected is None:
            expected = n
        target, x_in = batch.get_target(), batch.get_input()
        sliced = None
        if n < expected:
            # ragged eval tail: pad to the compiled shape, slice the pad rows
            # back off the OUTPUT before the metrics (targets stay unpadded) —
            # exact results, zero eval-graph recompiles across epochs
            with obs_span("val_pad"):
                padded = pad_minibatch(batch, expected)
            if padded is not None:
                x_in, sliced = padded[0].get_input(), n
        with obs_span("val_dispatch"):
            y = eval_step(params, model_state, _to_device_tree(x_in))
        if sliced is not None:
            y = jax.tree_util.tree_map(lambda a: a[:sliced], y)
        for m in methods:
            res = m(y, target)
            totals[m.name] = totals[m.name] + res if m.name in totals else res
    return totals


class LocalOptimizer(Optimizer):
    """Single-device training (reference: ``$DL/optim/LocalOptimizer.scala``).

    The reference's coreNumber-way model cloning + thread pool collapses into the
    one jitted train step below.
    """

    def _optimize_impl(self) -> AbstractModule:
        model, method = self.model, self.optim_method
        x0 = self._first_batch_input()
        self._validate_before_step(jax.eval_shape(lambda: x0))
        if not model.is_built():
            model.build(RandomGenerator.next_key(), jax.eval_shape(lambda: x0))
        self._audit_params()
        self._install_health()  # hooks seed state BEFORE the pytree is read
        params, model_state = model.get_parameters(), model.get_state()
        if self._precision is not None:
            raise ValueError(
                "low-precision policies (comms_dtype/master_dtype/slot_dtype) "
                "hang off the ZeRO-1 flat master buffer; use "
                "DistriOptimizer(parameter_sync='sharded'), which runs on a "
                "one-device mesh too"
            )
        slots = self._init_slots(method, params)
        return self._run_with_step(
            self._cached_standard_step(method), params, model_state, slots
        )
