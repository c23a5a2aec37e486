#!/usr/bin/env python
"""AST-based framework linter — enforces bigdl_tpu's own invariants.

Pure static analysis (no imports of the linted code, no jax): parses every
``.py`` file under the given paths and reports ``file:line: CODE message``
findings, exiting non-zero when any are found. Rules:

* **BDL001 unseeded-global-rng** — library code must not draw from the global
  ``numpy.random`` / stdlib ``random`` state (``np.random.randn`` etc.):
  results become irreproducible and differ across processes, which breaks the
  SPMD contract (every process must see the same stream). Use
  ``utils.random.RandomGenerator`` or an explicitly seeded
  ``np.random.default_rng(seed)``.
* **BDL002 host-sync-in-forward** — inside a jitted forward path (``_apply`` /
  ``_fn`` methods) there must be no host synchronization or host side effects:
  ``time.time()`` / ``time.perf_counter()``, ``.block_until_ready()``,
  ``.item()``, ``np.asarray``/``np.array`` materialization, or ``print``.
  These either block the device pipeline or silently fire only at trace time.
* **BDL003 mutable-default-arg** — no mutable default arguments (``[]``,
  ``{}``, ``set()``, ``list()``, ``dict()``) anywhere in library code; module
  constructors especially get cached in ``_ctor_spec`` for serialization, so a
  shared mutable default corrupts every later instance.
* **BDL004 missing-shape-contract** — every layer class defining a concrete
  ``_apply`` in the core ``nn`` layer files must expose an ``infer_shape``
  contract (defined in the class, inherited from a package base other than
  ``AbstractModule``, or assigned in the class body / at module level) so
  ``analysis.ShapeProp`` can check models without tracing.
* **BDL005 host-sync-in-hot-loop** — inside the hot-loop modules
  (optimizer/predictor step builders and drivers, ``HOT_LOOP_FILES``), nested
  functions — the jitted step bodies and per-iteration closures — must not
  contain host-sync idioms: ``float(...)`` on a non-literal, ``.item()``,
  ``np.asarray``/``np.array`` on traced values, or ``.block_until_ready()``.
  Each one either serializes dispatch against compute (the round-1 per-step
  ``float(loss)`` regression) or silently materializes at trace time. The
  deliberate one-step-late loss pull carries a suppression with its reason.
* **BDL006 wall-clock-duration** — in ``bigdl_tpu/`` library code, durations
  must come from ``time.perf_counter()``: ``time.time()`` appearing as an
  operand of a subtraction (``time.time() - t0`` and friends) is flagged —
  wall-clock is subject to NTP steps/smears, so a "duration" built from it
  can jump backwards or stall, silently corrupting step-time metrics and
  flush intervals. Plain ``time.time()`` EVENT TIMESTAMPS (telemetry ``ts``
  fields, tfevents ``wall_time``) are exempt — they are not subtractions.
* **BDL007 swallowed-fault** — in ``bigdl_tpu/`` library code, a bare
  ``except:`` (any body) or an ``except Exception:`` / ``except
  BaseException:`` handler whose body is only ``pass`` swallows faults the
  resilience FailurePolicy must see: the failure never reaches
  ``optimize()``'s classification, so no retry, no rollback, no telemetry —
  the run silently continues on corrupt state. Catch the narrowest type that
  can actually occur, or re-raise / log with the reason. Deliberate
  swallows carry a ``# lint: disable=BDL007`` suppression with the reason.
* **BDL008 obs-host-pull** — inside the observability package
  (``bigdl_tpu/obs/``), no ``jax.device_get`` and no ``np.asarray`` /
  ``np.array`` materialization: the obs layer's contract is ZERO added host
  syncs — every device value it reports must arrive through the existing
  one-step-late loss-pull seam, already paid for by the driver loop. A stray
  ``device_get`` in a hook or exporter silently serializes dispatch against
  compute on every step it touches. The single sanctioned pull
  (``HealthMonitor.snapshot``) carries a ``# lint: disable=BDL008`` with its
  reasoning; anything else must go through it.

* **BDL009 raw-pallas-call** — in ``bigdl_tpu/`` library code, every Pallas
  kernel launch must route through ``utils.compat.pallas_call`` (the
  interpret-fallback helper): a raw ``pl.pallas_call`` has no off-TPU story —
  it dies in the Mosaic compiler on CPU hosts, so the kernel would be
  untestable under the tier-1 ``JAX_PLATFORMS=cpu`` gate and would crash
  auto-selected paths on runtimes where Mosaic is broken. The helper resolves
  ``interpret=None`` per backend and carries the one sanctioned raw call.
* **BDL011 unbounded-hot-queue** — in the host input-pipeline hot modules
  (``PIPELINE_BOUNDED_FILES``: the dataset streaming/prefetch code and the
  optimizer driver), every ``queue.Queue()`` / ``collections.deque()`` must
  be constructed with an explicit bound (``maxsize=`` / ``maxlen=``, not
  None/0). These queues sit between producer and consumer THREADS; an
  unbounded one turns any consumer stall into unbounded host-memory growth —
  decoded batches pin big buffers fast. Use
  ``dataset.pipeline.StagingRing`` (bounded + event-aware close) or pass an
  explicit bound.
* **BDL010 sync-on-batching-thread** — inside the serving batcher's
  admit/flush hot loop (``SERVING_HOT_FILES``: ``serving/batcher.py``, every
  function), no blocking host sync: ``float(...)`` on a non-literal,
  ``.item()``, ``np.asarray``/``np.array``, or ``.block_until_ready()``. The
  batching thread is SHARED by every caller of a model — one device sync
  there serializes all concurrent requests behind one transfer. Per-request
  materialization belongs in the caller's future
  (``serving/queue.py::ServeFuture.result``), never on the batching thread;
  the only sampled pull (activation drift) lives behind ``obs/health.py``'s
  sanctioned seam.
* **BDL012 pickle-on-artifact-payload** — in the artifact/manifest handling
  modules (``ARTIFACT_PAYLOAD_FILES``: the serving runtime and checkpoint
  serialization), no ``pickle.load``/``loads``/``Unpickler`` and no
  ``np.load(..., allow_pickle=True)``: these modules consume bytes from
  SHARED artifact stores and checkpoint dirs, and unpickling such payloads
  executes arbitrary code on every replica that mounts the store. Artifact
  payloads go through ``utils/aot.py``'s verified loader —
  ``jax.export.deserialize`` (a StableHLO parser) + ``json`` manifests with
  sha256 verify-on-load — which is the one exempt file.

* **BDL014 unsupervised-serving-thread** — under ``bigdl_tpu/serving/``,
  every worker thread must be spawned through the supervised seam
  (``serving/resilience.py::spawn_worker``): a raw ``threading.Thread(...)``
  there is a worker nobody supervises — unnamed in hung-process dumps,
  possibly non-daemon (pins a dying process), and invisible to the
  ``ServingSupervisor``'s liveness/heartbeat checks, so its death silently
  hangs every caller blocked on one of its futures. The helper itself
  carries the one sanctioned suppression.

* **BDL015 device-touch-in-scrape-plane** — the observability scrape
  endpoint (``EXPORT_DEVICE_FREE_FILES``: ``obs/export.py``) must be
  device-free BY CONSTRUCTION: no ``jax``/``jax.numpy`` import and no call
  through a jax alias anywhere in the module. Its handlers run on an HTTP
  thread that any scraper can hit at any time — a jax call there could
  initialize a backend, trigger a transfer, or block a dispatch mid-scrape,
  silently breaking the zero-new-host-syncs contract for every request.
  Everything ``/healthz``/``/metrics`` serve must come from host-side state
  the telemetry ring and health snapshots already hold.

* **BDL016 unsanctioned-perf-introspection** — in ``bigdl_tpu/`` library
  code, HLO/lowered-program cost introspection (``*.cost_analysis()``) and
  ``jax.profiler`` CAPTURE calls (``start_trace``/``stop_trace``/``trace``
  — the annotation APIs stay free) are banned outside the two sanctioned
  seams: ``obs/profiler.py`` (the cost-model/introspection module) and
  ``obs/perf.py`` (the accounting + capture-serialization layer). A stray
  ``cost_analysis`` compiles programs behind the telemetry layer's back
  (double compiles, unattributed wall time), and a raw ``start_trace``
  next to the serialized capture seam aborts whichever window already
  holds the process-wide profiler. Route cost questions through
  ``obs.profiler.cost_summary``/``lowered_cost_summary`` and captures
  through ``obs.perf.start_capture``/``stop_capture``.

* **BDL013 silent-dtype-promotion** — in the low-precision comms/
  quantization hot modules (``optim/quantization.py``,
  ``parallel/compression.py``, ``tensor/quantized.py``, ``nn/quantized.py``)
  every array constructor must spell its dtype (a dtype-less ``jnp.zeros``/
  ``ones``/``arange``/``full``/``empty`` silently mints f32/int32 — in code
  whose whole job is controlling precision, an implicit dtype is a landmine),
  and a bare ``.astype(jnp.float32)`` may appear only at the sanctioned
  dequant seams (which carry a ``# lint: disable=BDL013`` naming the seam) —
  anywhere else it silently re-promotes a deliberately low-precision value.

* **BDL017 unguarded-cross-thread-state** — (concurrency auditor,
  ``bigdl_tpu/analysis/concurrency.py``, over the threaded-subsystem files)
  an attribute guarded by a lock — annotated ``# guarded-by: _lock`` on its
  ``__init__`` assignment, or inferred because every non-init write holds one
  common lock — read or written without that lock from a function reachable
  by more than one thread entry (main callers, ``spawn_worker``/``Thread``
  workers, ``MonitorBase`` poll loops, ``http.server`` handlers). Deliberate
  unlocked reads (monotone counters, latest-wins gauges) carry a suppression
  stating the invariant that makes them safe.
* **BDL018 wait-notify-blocking-discipline** — (concurrency auditor)
  ``Condition.wait`` must sit inside a ``while``-predicate loop with its
  condition held (wakeups are advisory), ``notify``/``notify_all`` must hold
  the condition, and known-blocking calls (``sleep``, ``join``,
  ``Future.result``/``Queue.get``/``put`` without timeout, socket/HTTP,
  ``np.asarray``/``.item()``/``.block_until_ready()`` materialization) are
  banned inside ``with`` blocks of locks annotated ``# hot-lock`` — one
  blocked holder stalls every thread contending for the lock.
* **BDL019 lock-order-cycle** — (concurrency auditor) every statically
  visible nested acquisition (including one-call-deep interprocedural:
  holding A while calling a method that takes B) is an edge in the directed
  lock-order graph; a cycle means two threads can take the locks in opposite
  orders and deadlock. The runtime half (``analysis/lock_tracer.py``,
  ``BIGDL_LOCK_DEBUG=1``) cross-checks observed orders against this graph.
* **BDL021 raw-collective-outside-parallel** — in ``bigdl_tpu/`` library
  code outside ``bigdl_tpu/parallel/``, a direct ``lax.ppermute`` /
  ``lax.all_to_all`` call is a hand-rolled collective schedule: route it
  through the parallel helpers (``pipeline_apply``, ``moe_ffn``,
  ``ring_attention``, the compression codec) so mesh-axis conventions,
  donation discipline, and the PerfAccountant comms decomposition
  (ppermute/all_to_all byte classification) stay centralized in the one
  package that owns them.
* **BDL022 unpropagated-trace-context** — in ``bigdl_tpu/`` modules that use
  the causal-tracing seam (``obs.trace``), a raw ``threading.Thread``
  construction severs the trace: thread-local ``TraceContext`` (and the
  bound ``SpanCollector``) does NOT cross the spawn, so every span the
  worker opens is an orphan. Spawn through
  ``serving/resilience.spawn_worker`` (which captures and re-binds the
  spawner's context), or have the enclosing function hand context across
  the seam itself (``bind_context`` / ``context_scope`` /
  ``bind_collector`` inside the thread target). An explicit
  ``spawn_worker(..., context=None)`` severs deliberately and carries a
  suppression naming why the chain ends there.
* **BDL023 unsanctioned-process-topology** — in ``bigdl_tpu/`` library code
  outside the process-topology seams (``utils/engine.py`` and
  ``bigdl_tpu/parallel/``), ``jax.distributed.initialize(...)`` and raw jax
  mesh construction (``jax.sharding.Mesh(...)`` / ``jax.make_mesh(...)``)
  are banned: fleet identity (``process_index``/``process_count``) enters
  through ``Engine.init_distributed`` exactly once, and every mesh derived
  from it is built by ``Engine.mesh()`` or the parallel package's helpers
  (``make_mesh``). A stray mesh built from ``process_count`` elsewhere
  silently disagrees with the elastic coordinator's device-block
  arithmetic after a shrink/rejoin — survivors would train on one topology
  while checkpoints shard over another. The elastic coordinator's own
  mesh builders (``resilience/elastic.py``) are deliberate seams and
  carry suppressions naming that.
* **BDL024 dump-hook-bypass** — in ``bigdl_tpu/`` library code outside the
  sanctioned seams (``obs/blackbox.py``, ``resilience/preemption.py``),
  ``os._exit(...)``, a bare ``sys.exit(...)`` and ``signal.signal(...)``
  registration are banned: ``os._exit`` skips every ``finally``/``atexit``
  (the postmortem dump and the telemetry flush never run), a library-level
  ``sys.exit`` turns a typed failure an outer layer would dump-and-triage
  into a silent process death, and a stray ``signal.signal`` clobbers the
  ``PreemptionGuard``/faulthandler registrations the flight recorder
  depends on. ``sys.exit`` under an ``if __name__ == "__main__":`` guard
  (a module's CLI entry) is exempt — that IS the process's outermost
  layer.

Suppression: append ``# lint: disable=BDL00X`` to the offending line (the
``class`` line for BDL004), or put ``# lint: disable-file=BDL00X`` in the
first 10 lines of the file. Suppressions should carry a short reason in the
same comment.

Usage::

    python tools/lint_framework.py bigdl_tpu/            # lint the library
    python tools/lint_framework.py --rules               # print rule docs
"""

from __future__ import annotations

import argparse
import ast
import os
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

# files (relative to a bigdl_tpu/nn/ directory) where BDL004 is enforced; the
# remaining layer files (recurrent, attention, detection, ...) intentionally
# resolve through the jax.eval_shape fallback — see docs/analysis.md
CORE_CONTRACT_FILES = {
    "module.py", "graph.py", "linear.py", "conv.py", "pooling.py",
    "activations.py", "dropout.py", "normalization.py", "embedding.py",
    "structural.py", "table_ops.py", "math_ops.py", "remat.py", "moe.py",
}

NP_RANDOM_ALLOWED = {"default_rng", "Generator", "SeedSequence", "BitGenerator",
                     "PCG64", "Philox"}
PY_RANDOM_BANNED = {
    "random", "randint", "uniform", "choice", "choices", "shuffle", "sample",
    "randrange", "gauss", "normalvariate", "betavariate", "expovariate",
    "triangular", "vonmisesvariate", "paretovariate", "weibullvariate",
    "getrandbits", "randbytes",
}
TIME_BANNED = {"time", "perf_counter", "monotonic", "process_time"}
FORWARD_FN_NAMES = {"_apply", "_fn"}

# the sanctioned trace-context carriers across a thread seam (BDL022): a
# spawn site whose enclosing function touches one of these is handing the
# spawner's TraceContext / SpanCollector across itself
_CTX_PROP_NAMES = {"bind_context", "context_scope", "bind_collector",
                   "spawn_worker"}

# per-iteration hot-loop modules (BDL005): files whose NESTED functions are
# jitted step bodies or per-step closures — a host sync there stalls every step
HOT_LOOP_FILES = (
    "optim/local_optimizer.py",
    "optim/predictor.py",
    "parallel/distri_optimizer.py",
    "parallel/hybrid.py",
    "parallel/parameter.py",
)

# serving batching-thread modules (BDL010): EVERY function body is the hot
# loop — the worker admits/flushes for all of a model's concurrent callers,
# so a single blocking sync there stalls them all
SERVING_HOT_FILES = (
    "serving/batcher.py",
)

# host input-pipeline hot modules (BDL011): queues here sit between
# producer/consumer threads of the streaming data plane — every one must be
# bounded or a stalled consumer grows host memory without limit
PIPELINE_BOUNDED_FILES = (
    "dataset/dataset.py",
    "dataset/files.py",
    "dataset/pipeline.py",
    "dataset/tfrecord.py",
    "optim/local_optimizer.py",
)

# artifact/manifest payload modules (BDL012): these files handle bytes that
# arrive from a SHARED artifact store or a checkpoint dir — unpickling such
# payloads is arbitrary code execution on every replica that mounts the
# store. Artifact payloads load ONLY through utils/aot.py's verified loader
# (jax.export.deserialize — a StableHLO parser — plus json manifests), which
# is why aot.py itself is the one exempt file.
# low-precision comms/quantization hot modules (BDL013): these files exist
# to CONTROL dtypes — every constructor spells its dtype and f32 upcasts
# happen only at named dequant seams
QUANT_HOT_FILES = (
    "optim/quantization.py",
    "parallel/compression.py",
    "tensor/quantized.py",
    "nn/quantized.py",
)

ARTIFACT_PAYLOAD_FILES = (
    "serving/server.py",
    "serving/artifacts.py",
    "serving/batcher.py",
    "serving/queue.py",
    "utils/serialization.py",
)

# the device-free scrape plane (BDL015): the HTTP endpoint module serves
# /healthz + /metrics from ring/health state alone — importing or calling
# jax there puts devices one scrape away from a surprise sync
EXPORT_DEVICE_FREE_FILES = (
    "obs/export.py",
)

# the sanctioned perf-introspection seams (BDL016): cost_analysis() and
# jax.profiler capture calls live ONLY here — obs/profiler.py owns the
# lowered-program introspection, obs/perf.py the accounting + the
# process-wide capture serialization every trace window must go through
PERF_INTROSPECTION_FILES = (
    "obs/profiler.py",
    "obs/perf.py",
)

# jax.profiler CAPTURE entry points (BDL016). TraceAnnotation /
# StepTraceAnnotation are annotations, not captures, and stay free.
_PROFILER_CAPTURE_NAMES = ("start_trace", "stop_trace", "trace")

# hand-rolled collective schedules (BDL021): these lax primitives belong to
# bigdl_tpu/parallel/'s helpers only (psum/all_gather etc. stay free — they
# are reduction idioms, not point-to-point schedules)
_RAW_COLLECTIVE_NAMES = ("ppermute", "all_to_all")


@dataclass
class Finding:
    path: str
    line: int
    code: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.code} {self.message}"


def _suppressed(src_lines: Sequence[str], lineno: int, code: str) -> bool:
    if not 1 <= lineno <= len(src_lines):
        return False
    text = src_lines[lineno - 1]
    if "lint: disable=" in text and code in text.split("lint: disable=", 1)[1]:
        return True
    for head in src_lines[:10]:
        if "lint: disable-file=" in head and code in head.split(
            "lint: disable-file=", 1
        )[1]:
            return True
    return False


class _Aliases(ast.NodeVisitor):
    """Track module aliases: numpy as np, time, random, numpy.random as ..."""

    def __init__(self):
        self.numpy: Set[str] = set()
        self.numpy_random: Set[str] = set()
        self.time: Set[str] = set()
        self.random: Set[str] = set()
        self.from_random: Set[str] = set()  # names imported from stdlib random
        self.jax: Set[str] = set()
        self.from_jax: Set[str] = set()  # device_get imported by name
        self.pallas: Set[str] = set()  # jax.experimental.pallas module aliases
        self.from_pallas: Set[str] = set()  # pallas_call imported by name
        self.queue_mod: Set[str] = set()  # stdlib queue module aliases
        self.from_queue: Set[str] = set()  # Queue imported by name
        self.collections_mod: Set[str] = set()  # collections module aliases
        self.from_collections_deque: Set[str] = set()  # deque by name
        self.pickle_mod: Set[str] = set()  # pickle module aliases (BDL012)
        self.from_pickle: Set[str] = set()  # load/loads/Unpickler by name
        self.jnp: Set[str] = set()  # jax.numpy module aliases (BDL013)
        self.threading_mod: Set[str] = set()  # threading aliases (BDL014)
        self.from_threading_thread: Set[str] = set()  # Thread by name
        self.from_jax_profiler: Set[str] = set()  # capture fns by name (BDL016)
        self.profiler_mod: Set[str] = set()  # jax.profiler module aliases
        self.lax: Set[str] = set()  # jax.lax module aliases (BDL021)
        self.from_lax: Set[str] = set()  # ppermute/all_to_all by name
        self.trace_mod: Set[str] = set()  # obs.trace module aliases (BDL022)
        self.from_trace: Set[str] = set()  # names imported from obs.trace
        self.sharding_mod: Set[str] = set()  # jax.sharding aliases (BDL023)
        self.from_sharding_mesh: Set[str] = set()  # Mesh/make_mesh by name
        self.distributed_mod: Set[str] = set()  # jax.distributed aliases
        self.from_jax_distributed: Set[str] = set()  # initialize by name
        self.os_mod: Set[str] = set()  # os module aliases (BDL024)
        self.sys_mod: Set[str] = set()  # sys module aliases (BDL024)
        self.signal_mod: Set[str] = set()  # signal module aliases (BDL024)
        self.from_os_exit: Set[str] = set()  # os._exit imported by name
        self.from_sys_exit: Set[str] = set()  # sys.exit imported by name
        self.from_signal_signal: Set[str] = set()  # signal.signal by name

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            top, alias = a.name, a.asname or a.name.split(".")[0]
            if top == "numpy":
                self.numpy.add(alias)
            elif top == "numpy.random":
                self.numpy_random.add(a.asname or "numpy")
            elif top == "time":
                self.time.add(alias)
            elif top == "random":
                self.random.add(alias)
            elif top == "pickle":
                self.pickle_mod.add(alias)
            elif top == "queue":
                self.queue_mod.add(alias)
            elif top == "threading":
                self.threading_mod.add(alias)
            elif top == "collections":
                self.collections_mod.add(alias)
            elif top == "os":
                self.os_mod.add(alias)
            elif top == "sys":
                self.sys_mod.add(alias)
            elif top == "signal":
                self.signal_mod.add(alias)
            elif top == "jax" or top.startswith("jax."):
                self.jax.add(alias)
            if top == "jax.numpy" and a.asname:
                self.jnp.add(a.asname)
            if top == "jax.profiler" and a.asname:
                self.profiler_mod.add(a.asname)  # import jax.profiler as jp
            if top == "jax.lax" and a.asname:
                self.lax.add(a.asname)  # import jax.lax as lax
            if top == "jax.experimental.pallas" and a.asname:
                self.pallas.add(a.asname)
            if top == "jax.sharding" and a.asname:
                self.sharding_mod.add(a.asname)  # BDL023
            if top == "jax.distributed" and a.asname:
                self.distributed_mod.add(a.asname)  # BDL023
            if top == "bigdl_tpu.obs.trace" and a.asname:
                self.trace_mod.add(a.asname)  # BDL022

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "numpy" :
            for a in node.names:
                if a.name == "random":
                    self.numpy_random.add(a.asname or a.name)
        elif node.module == "random":
            for a in node.names:
                if a.name in PY_RANDOM_BANNED:
                    self.from_random.add(a.asname or a.name)
        elif node.module == "jax":
            for a in node.names:
                if a.name == "device_get":
                    self.from_jax.add(a.asname or a.name)
                elif a.name == "numpy":
                    self.jnp.add(a.asname or a.name)
                elif a.name == "profiler":
                    self.profiler_mod.add(a.asname or a.name)
                elif a.name == "lax":
                    self.lax.add(a.asname or a.name)
                elif a.name == "sharding":
                    self.sharding_mod.add(a.asname or a.name)
                elif a.name == "distributed":
                    self.distributed_mod.add(a.asname or a.name)
                elif a.name == "make_mesh":
                    self.from_sharding_mesh.add(a.asname or a.name)
        elif node.module == "jax.sharding":
            for a in node.names:
                if a.name == "Mesh":
                    self.from_sharding_mesh.add(a.asname or a.name)
        elif node.module == "jax.distributed":
            for a in node.names:
                if a.name == "initialize":
                    self.from_jax_distributed.add(a.asname or a.name)
        elif node.module == "jax.lax":
            for a in node.names:
                if a.name in _RAW_COLLECTIVE_NAMES:
                    self.from_lax.add(a.asname or a.name)
        elif node.module == "jax.experimental":
            for a in node.names:
                if a.name == "pallas":
                    self.pallas.add(a.asname or a.name)
        elif node.module == "jax.experimental.pallas":
            for a in node.names:
                if a.name == "pallas_call":
                    self.from_pallas.add(a.asname or a.name)
        elif node.module == "pickle":
            for a in node.names:
                if a.name in ("load", "loads", "Unpickler"):
                    self.from_pickle.add(a.asname or a.name)
        elif node.module == "queue":
            for a in node.names:
                if a.name in ("Queue", "LifoQueue", "PriorityQueue", "SimpleQueue"):
                    self.from_queue.add(a.asname or a.name)
        elif node.module == "collections":
            for a in node.names:
                if a.name == "deque":
                    self.from_collections_deque.add(a.asname or a.name)
        elif node.module == "threading":
            for a in node.names:
                if a.name == "Thread":
                    self.from_threading_thread.add(a.asname or a.name)
        elif node.module == "os":
            for a in node.names:
                if a.name == "_exit":
                    self.from_os_exit.add(a.asname or a.name)
        elif node.module == "sys":
            for a in node.names:
                if a.name == "exit":
                    self.from_sys_exit.add(a.asname or a.name)
        elif node.module == "signal":
            for a in node.names:
                if a.name == "signal":
                    self.from_signal_signal.add(a.asname or a.name)
        elif node.module == "jax.profiler":
            for a in node.names:
                if a.name in _PROFILER_CAPTURE_NAMES:
                    self.from_jax_profiler.add(a.asname or a.name)
        # obs.trace imports (BDL022) — all the library's spellings: absolute
        # (bigdl_tpu.obs.trace), relative (..obs / ..obs.trace / . / .trace)
        mod = node.module or ""
        if mod.endswith("obs.trace") or (mod == "trace" and node.level >= 1):
            for a in node.names:
                self.from_trace.add(a.asname or a.name)
        elif mod.endswith("obs") or (mod == "" and node.level >= 1):
            for a in node.names:
                if a.name == "trace":
                    self.trace_mod.add(a.asname or a.name)


def _attr_chain(node: ast.AST) -> Optional[Tuple[str, ...]]:
    """('np', 'random', 'randn') for np.random.randn; None for non-name roots."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, src: str, tree: ast.AST):
        self.path = path
        self.src_lines = src.split("\n")
        self.aliases = _Aliases()
        self.aliases.visit(tree)
        self.findings: List[Finding] = []
        self._forward_depth = 0
        self._func_depth = 0
        # BDL022: per enclosing function, does its body (nested defs
        # included) hand trace context/collector across the thread seam?
        self._ctxprop_stack: List[bool] = []
        norm = path.replace(os.sep, "/")
        self._hot_loop = norm.endswith(HOT_LOOP_FILES)
        self._serving_hot = norm.endswith(SERVING_HOT_FILES)
        self._pipeline_bounded = norm.endswith(PIPELINE_BOUNDED_FILES)
        self._artifact_scope = norm.endswith(ARTIFACT_PAYLOAD_FILES)
        self._quant_scope = norm.endswith(QUANT_HOT_FILES)
        self._export_scope = norm.endswith(EXPORT_DEVICE_FREE_FILES)
        self._perf_sanctioned = norm.endswith(PERF_INTROSPECTION_FILES)
        # BDL014 scope: the whole serving package — every thread there must
        # come from the supervised spawn seam
        nparts = norm.split("/")
        self._serving_scope = (
            "bigdl_tpu" in nparts
            and "serving" in nparts[nparts.index("bigdl_tpu"):]
        )
        # BDL006/BDL007 scope: the library proper (tools/tests keep their own
        # idioms)
        self._duration_rule = "bigdl_tpu" in norm.split("/")
        self._library_scope = self._duration_rule
        # BDL008 scope: the observability package — its zero-added-host-sync
        # contract bans device_get / numpy materialization outside the one
        # sanctioned (suppressed) pull seam
        parts = norm.split("/")
        self._obs_scope = (
            "bigdl_tpu" in parts and "obs" in parts[parts.index("bigdl_tpu"):]
        )
        # BDL021 scope: the library minus the one package sanctioned to spell
        # raw collective schedules
        self._parallel_sanctioned = (
            "bigdl_tpu" in parts
            and "parallel" in parts[parts.index("bigdl_tpu"):]
        )
        # BDL023 scope: the process-topology seams — Engine owns
        # jax.distributed.initialize and the base mesh, bigdl_tpu/parallel/
        # owns every mesh-from-process_count derivation
        self._topology_sanctioned = (
            self._parallel_sanctioned or norm.endswith("utils/engine.py")
        )
        # BDL022 scope: library modules that use the causal-tracing seam —
        # only there can a raw thread spawn orphan an active span
        self._trace_scope = self._library_scope and bool(
            self.aliases.trace_mod or self.aliases.from_trace
        )
        # BDL024 scope: the process-exit / signal-handler seams — only the
        # flight recorder (faulthandler arming) and the preemption guard
        # (SIGTERM chain) may install handlers or bypass teardown
        self._exit_sanctioned = norm.endswith(
            ("obs/blackbox.py", "resilience/preemption.py")
        )
        # BDL024: sys.exit under `if __name__ == "__main__":` is CLI
        # plumbing, not library control flow — track the guard depth
        self._main_guard_depth = 0

    # ------------------------------------------------------------- reporting
    def _report(self, node: ast.AST, code: str, message: str) -> None:
        line = getattr(node, "lineno", 1)
        if not _suppressed(self.src_lines, line, code):
            self.findings.append(Finding(self.path, line, code, message))

    # ----------------------------------------------------------------- rules
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._check_mutable_defaults(node)
        in_forward = node.name in FORWARD_FN_NAMES
        if in_forward:
            self._forward_depth += 1
        self._func_depth += 1
        self._ctxprop_stack.append(any(
            (isinstance(n, ast.Name) and n.id in _CTX_PROP_NAMES)
            or (isinstance(n, ast.Attribute) and n.attr in _CTX_PROP_NAMES)
            for n in ast.walk(node)
        ))
        self.generic_visit(node)
        self._ctxprop_stack.pop()
        self._func_depth -= 1
        if in_forward:
            self._forward_depth -= 1

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_If(self, node: ast.If) -> None:
        # BDL024: `if __name__ == "__main__":` exempts sys.exit in its body
        guard = (
            isinstance(node.test, ast.Compare)
            and isinstance(node.test.left, ast.Name)
            and node.test.left.id == "__name__"
            and len(node.test.ops) == 1
            and isinstance(node.test.ops[0], ast.Eq)
            and isinstance(node.test.comparators[0], ast.Constant)
            and node.test.comparators[0].value == "__main__"
        )
        if guard:
            self._main_guard_depth += 1
        self.generic_visit(node)
        if guard:
            self._main_guard_depth -= 1

    def _check_mutable_defaults(self, node) -> None:
        for default in list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]:
            bad = isinstance(default, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(default, ast.Call)
                and isinstance(default.func, ast.Name)
                and default.func.id in ("list", "dict", "set")
            )
            if bad:
                self._report(
                    default,
                    "BDL003",
                    f"mutable default argument in {node.name}(); default to "
                    "None and allocate inside the body",
                )

    # ------------------------------------------------------ BDL015 (imports)
    _EXPORT_MSG = (
        "in the scrape-plane module (obs/export.py): the endpoint is "
        "device-free BY CONSTRUCTION — its HTTP handlers must serve only "
        "host-side ring/health state, so a scrape can never initialize a "
        "backend, trigger a transfer, or block a dispatch (BDL015)"
    )

    def visit_Import(self, node: ast.Import) -> None:
        if self._export_scope:
            for a in node.names:
                if a.name.split(".")[0] == "jax":
                    self._report(
                        node, "BDL015", f"import {a.name} {self._EXPORT_MSG}"
                    )
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if (
            self._export_scope
            and node.module is not None
            and node.module.split(".")[0] == "jax"
        ):
            self._report(
                node, "BDL015", f"from {node.module} import "
                f"{', '.join(a.name for a in node.names)} {self._EXPORT_MSG}"
            )
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        if (
            self._forward_depth
            and isinstance(node.func, ast.Name)
            and node.func.id == "print"
        ):
            self._report(
                node,
                "BDL002",
                "print() inside a jitted forward (_apply/_fn) only fires at "
                "trace time; use jax.debug.print or drop it",
            )
        in_hot_nested = self._hot_loop and self._func_depth >= 2
        in_serving_hot = self._serving_hot and self._func_depth >= 1
        if (
            in_hot_nested
            and isinstance(node.func, ast.Name)
            and node.func.id == "float"
            and node.args
            and not isinstance(node.args[0], ast.Constant)
        ):
            self._report(
                node,
                "BDL005",
                "float() in a hot-loop closure forces a device->host pull "
                "every iteration, serializing dispatch against compute; pull "
                "late (one step behind) or keep the value on device",
            )
        if (
            in_serving_hot
            and isinstance(node.func, ast.Name)
            and node.func.id == "float"
            and node.args
            and not isinstance(node.args[0], ast.Constant)
        ):
            self._report(
                node,
                "BDL010",
                "float() on the serving batching thread can block on a "
                "device value, stalling every concurrent caller; per-request "
                "materialization belongs in the caller's future "
                "(ServeFuture.result), never in the admit/flush loop",
            )
        if self._pipeline_bounded:
            self._check_unbounded_queue(node)
        if self._artifact_scope:
            self._check_artifact_pickle(node)
        if self._quant_scope:
            self._check_quant_dtype(node)
        if self._serving_scope:
            self._check_unsupervised_thread(node)
        if self._trace_scope:
            self._check_unpropagated_context(node)
        if self._export_scope:
            chain0 = _attr_chain(node.func)
            root = (
                chain0[0] if chain0
                else node.func.id if isinstance(node.func, ast.Name)
                else None
            )
            if root is not None and (
                root in self.aliases.jax
                or root in self.aliases.jnp
                or root in self.aliases.from_jax
            ):
                self._report(
                    node, "BDL015",
                    f"{'.'.join(chain0) if chain0 else root}() call through "
                    f"a jax alias {self._EXPORT_MSG}",
                )
        chain = _attr_chain(node.func)
        if chain and len(chain) > 1:
            self._check_rng(node, chain)
            if self._forward_depth:
                self._check_host_sync(node, chain)
            if in_hot_nested:
                self._check_hot_loop_sync(node, chain)
            if in_serving_hot:
                self._check_serving_sync(node, chain)
            if self._obs_scope:
                self._check_obs_host_pull(node, chain)
            if self._library_scope:
                self._check_raw_pallas_call(node, chain)
            if self._library_scope and not self._perf_sanctioned:
                self._check_perf_introspection(node, chain)
            if self._library_scope and not self._parallel_sanctioned:
                self._check_raw_collective(node, chain)
            if self._library_scope and not self._topology_sanctioned:
                self._check_process_topology(node, chain)
            if self._library_scope and not self._exit_sanctioned:
                self._check_exit_bypass(node, chain)
        if (
            self._library_scope
            and not self._perf_sanctioned
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "cost_analysis"
        ):
            # attribute-level (not chain-based): the usual spelling chains
            # off a call result — fn.lower(...).compile().cost_analysis()
            self._report(
                node,
                "BDL016",
                "cost_analysis() outside the sanctioned obs/profiler.py + "
                "obs/perf.py seams; route cost questions through "
                "obs.profiler.cost_summary / lowered_cost_summary (one "
                "introspection seam keeps compile accounting honest)",
            )
        if (
            self._library_scope
            and not self._perf_sanctioned
            and isinstance(node.func, ast.Name)
            and node.func.id in self.aliases.from_jax_profiler
        ):
            self._report(
                node,
                "BDL016",
                f"{node.func.id}() imported straight from jax.profiler is an "
                "unserialized capture call; route trace windows through "
                "obs.perf.start_capture/stop_capture (the sanctioned seam "
                "that keeps concurrent windows from aborting each other)",
            )
        if (
            self._library_scope
            and not self._parallel_sanctioned
            and isinstance(node.func, ast.Name)
            and node.func.id in self.aliases.from_lax
        ):
            self._report(
                node,
                "BDL021",
                f"raw {node.func.id}() outside bigdl_tpu/parallel/ is a "
                "hand-rolled collective schedule; route it through the "
                "parallel helpers (pipeline_apply / moe_ffn / "
                "ring_attention) so mesh conventions and the perf comms "
                "decomposition stay centralized",
            )
        if (
            self._library_scope
            and not self._topology_sanctioned
            and isinstance(node.func, ast.Name)
        ):
            if node.func.id in self.aliases.from_sharding_mesh:
                self._report(
                    node,
                    "BDL023",
                    f"{node.func.id}() builds a jax mesh outside the "
                    "process-topology seams (utils/engine.py + "
                    "bigdl_tpu/parallel/); build meshes through Engine.mesh() "
                    "or parallel.make_mesh so the topology derived from "
                    "process_count stays consistent with the elastic "
                    "coordinator's device-block arithmetic",
                )
            elif node.func.id in self.aliases.from_jax_distributed:
                self._report(
                    node,
                    "BDL023",
                    f"{node.func.id}() imported from jax.distributed outside "
                    "Engine.init_distributed; fleet identity "
                    "(process_index/process_count) enters through the one "
                    "Engine seam so every subsystem agrees on membership",
                )
        if (
            self._library_scope
            and isinstance(node.func, ast.Name)
            and node.func.id in self.aliases.from_pallas
        ):
            self._report(
                node,
                "BDL009",
                f"{node.func.id}() imported straight from "
                "jax.experimental.pallas bypasses the interpret fallback; "
                "route kernels through utils.compat.pallas_call so they "
                "degrade to interpret mode off-TPU",
            )
        if (
            self._obs_scope
            and isinstance(node.func, ast.Name)
            and node.func.id in self.aliases.from_jax
        ):
            self._report(
                node,
                "BDL008",
                f"{node.func.id}() in obs code is a device->host pull; the "
                "obs layer adds ZERO host syncs — route the value through "
                "the one-step-late HealthMonitor.snapshot seam",
            )
        if (
            self._library_scope
            and not self._exit_sanctioned
            and isinstance(node.func, ast.Name)
        ):
            fid = node.func.id
            if fid in self.aliases.from_os_exit:
                self._report(node, "BDL024", self._EXIT_OS_MSG)
            elif (
                fid in self.aliases.from_sys_exit
                and not self._main_guard_depth
            ):
                self._report(node, "BDL024", self._EXIT_SYS_MSG)
            elif fid in self.aliases.from_signal_signal:
                self._report(node, "BDL024", self._EXIT_SIGNAL_MSG)
        if (
            isinstance(node.func, ast.Name)
            and node.func.id in self.aliases.from_random
        ):
            self._report(
                node,
                "BDL001",
                f"stdlib random.{node.func.id}() draws from the unseeded "
                "process-global stream; use utils.random.RandomGenerator",
            )
        self.generic_visit(node)

    def visit_BinOp(self, node: ast.BinOp) -> None:
        if self._duration_rule and isinstance(node.op, ast.Sub):
            for side in (node.left, node.right):
                if not isinstance(side, ast.Call):
                    continue
                chain = _attr_chain(side.func)
                if (
                    chain
                    and len(chain) == 2
                    and chain[0] in self.aliases.time
                    and chain[1] == "time"
                ):
                    self._report(
                        side,
                        "BDL006",
                        "time.time() used for a duration (operand of a "
                        "subtraction): wall-clock jumps under NTP — use "
                        "time.perf_counter() for intervals; time.time() is "
                        "for event timestamps only",
                    )
        self.generic_visit(node)

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        if self._library_scope:
            self._check_swallowed_fault(node)
        self.generic_visit(node)

    def _check_swallowed_fault(self, node: ast.ExceptHandler) -> None:
        if node.type is None:
            self._report(
                node,
                "BDL007",
                "bare except: swallows every fault (including the typed "
                "resilience exceptions the FailurePolicy classifies); catch "
                "the narrowest exception that can occur",
            )
            return

        def broad(t: ast.AST) -> bool:
            return isinstance(t, ast.Name) and t.id in ("Exception", "BaseException")

        types = node.type.elts if isinstance(node.type, ast.Tuple) else [node.type]
        if not any(broad(t) for t in types):
            return
        body = [
            s for s in node.body
            if not (isinstance(s, ast.Expr) and isinstance(s.value, ast.Constant))
        ]
        if all(isinstance(s, ast.Pass) for s in body):
            self._report(
                node,
                "BDL007",
                "except Exception: pass silently swallows faults the "
                "FailurePolicy should see (no retry, no rollback, no "
                "telemetry); handle, log, or re-raise",
            )

    def _check_rng(self, node: ast.Call, chain: Tuple[str, ...]) -> None:
        root = chain[0]
        # np.random.X(...) / numpy.random.X(...)
        if (
            len(chain) >= 3
            and root in self.aliases.numpy
            and chain[1] == "random"
            and chain[2] not in NP_RANDOM_ALLOWED
        ):
            self._report(
                node,
                "BDL001",
                f"{'.'.join(chain)}() draws from numpy's process-global RNG; "
                "seed explicitly via np.random.default_rng(seed) or "
                "utils.random.RandomGenerator",
            )
        # nprandom.X(...) where numpy.random was imported directly
        elif (
            len(chain) >= 2
            and root in self.aliases.numpy_random
            and chain[1] not in NP_RANDOM_ALLOWED
        ):
            self._report(
                node,
                "BDL001",
                f"{'.'.join(chain)}() draws from numpy's process-global RNG",
            )
        elif (
            len(chain) == 2
            and root in self.aliases.random
            and chain[1] in PY_RANDOM_BANNED
        ):
            self._report(
                node,
                "BDL001",
                f"{'.'.join(chain)}() draws from the unseeded process-global "
                "stream; use utils.random.RandomGenerator",
            )

    def _check_hot_loop_sync(self, node: ast.Call, chain: Tuple[str, ...]) -> None:
        if chain[-1] == "item" and not node.args and not node.keywords:
            self._report(
                node,
                "BDL005",
                ".item() in a hot-loop closure is a per-iteration "
                "device->host sync",
            )
        elif chain[-1] == "block_until_ready":
            self._report(
                node,
                "BDL005",
                ".block_until_ready() in a hot-loop closure stalls the "
                "dispatch pipeline",
            )
        elif len(chain) >= 2 and chain[0] in self.aliases.numpy and chain[-1] in (
            "asarray", "array",
        ):
            self._report(
                node,
                "BDL005",
                f"{'.'.join(chain)}() in a hot-loop closure materializes a "
                "traced/device value on host every iteration; use jnp or "
                "hoist it out of the loop",
            )

    def _check_serving_sync(self, node: ast.Call, chain: Tuple[str, ...]) -> None:
        """BDL010: the serving batcher's admit/flush loop must never block on
        a device value — it is one thread shared by every concurrent caller
        of the model. The caller-side future owns the materialization sync;
        the sampled drift pull lives behind obs/health.py's sanctioned
        seam."""
        if chain[-1] == "item" and not node.args and not node.keywords:
            self._report(
                node,
                "BDL010",
                ".item() on the serving batching thread is a device->host "
                "sync stalling every queued request; materialize in the "
                "caller's future instead",
            )
        elif chain[-1] == "block_until_ready":
            self._report(
                node,
                "BDL010",
                ".block_until_ready() on the serving batching thread "
                "serializes every model's callers behind one dispatch; the "
                "future's result() is where waiting belongs",
            )
        elif len(chain) >= 2 and chain[0] in self.aliases.numpy and chain[-1] in (
            "asarray", "array",
        ):
            self._report(
                node,
                "BDL010",
                f"{'.'.join(chain)}() on the serving batching thread "
                "materializes a device value, blocking the admit/flush loop; "
                "resolve futures with device row views and let the caller's "
                "result() pay its own sync",
            )

    def _check_artifact_pickle(self, node: ast.Call) -> None:
        """BDL012: pickle deserialization of artifact/manifest payloads is
        arbitrary code execution on every replica mounting the shared store;
        route loads through utils/aot.py's verified loader."""
        msg = (
            "deserializes an artifact/manifest payload with pickle — "
            "arbitrary code execution on every replica that mounts the "
            "store; route it through utils/aot.py's verified loader "
            "(jax.export.deserialize + json manifest with sha256 "
            "verify-on-load)"
        )
        if (
            isinstance(node.func, ast.Name)
            and node.func.id in self.aliases.from_pickle
        ):
            self._report(node, "BDL012", f"{node.func.id}() {msg}")
            return
        chain = _attr_chain(node.func)
        if not chain or len(chain) != 2:
            return
        if (
            chain[0] in self.aliases.pickle_mod
            and chain[1] in ("load", "loads", "Unpickler")
        ):
            self._report(node, "BDL012", f"pickle.{chain[1]}() {msg}")
        elif chain[0] in self.aliases.numpy and chain[1] == "load" and any(
            kw.arg == "allow_pickle"
            and isinstance(kw.value, ast.Constant)
            and kw.value.value
            for kw in node.keywords
        ):
            self._report(
                node,
                "BDL012",
                "np.load(allow_pickle=True) on an artifact/checkpoint "
                "payload can unpickle embedded objects — arbitrary code "
                "execution from a shared store; keep allow_pickle off "
                "(arrays only) or route through utils/aot.py's verified "
                "loader",
            )

    # minimum positional-arg count at which the dtype has been given
    # positionally (zeros(shape, dtype) etc.)
    _QUANT_CTOR_DTYPE_POS = {
        "zeros": 2, "ones": 2, "empty": 2, "full": 3, "arange": 4,
    }

    def _check_quant_dtype(self, node: ast.Call) -> None:
        """BDL013: the comms/quantization hot modules exist to CONTROL
        precision — a dtype-less jnp constructor silently mints f32/int32,
        and a bare ``.astype(jnp.float32)`` outside the sanctioned dequant
        seams silently re-promotes a deliberately low-precision value. The
        dequant seams carry the suppression naming themselves."""
        func = node.func
        chain = _attr_chain(func)
        ctor = None
        if chain is not None:
            if (
                len(chain) == 2
                and chain[0] in self.aliases.jnp
                and chain[1] in self._QUANT_CTOR_DTYPE_POS
            ):
                ctor = chain[1]
            elif (
                len(chain) == 3
                and chain[0] in self.aliases.jax
                and chain[1] == "numpy"
                and chain[2] in self._QUANT_CTOR_DTYPE_POS
            ):
                ctor = chain[2]
        if ctor is not None:
            has_dtype = any(kw.arg == "dtype" for kw in node.keywords)
            if not has_dtype and len(node.args) < self._QUANT_CTOR_DTYPE_POS[ctor]:
                self._report(
                    node,
                    "BDL013",
                    f"dtype-less jnp.{ctor}() in a quantization hot module "
                    "silently promotes to the default dtype; spell the dtype "
                    "explicitly — this code's whole job is precision control",
                )
        if (
            isinstance(func, ast.Attribute)
            and func.attr == "astype"
            and node.args
        ):
            a = node.args[0]
            ach = _attr_chain(a)
            is_f32 = (
                ach is not None
                and (
                    (len(ach) == 2 and ach[0] in self.aliases.jnp
                     and ach[1] == "float32")
                    or (len(ach) == 3 and ach[0] in self.aliases.jax
                        and ach[1] == "numpy" and ach[2] == "float32")
                    or (len(ach) == 1 and ach[0] == "float32")
                )
            )
            if is_f32:
                self._report(
                    node,
                    "BDL013",
                    "bare .astype(jnp.float32) in a quantization hot module "
                    "outside the sanctioned dequant seam silently re-promotes "
                    "a low-precision value; dequantize at a named seam "
                    "(suppressed with its reason) or keep the storage dtype",
                )

    def _check_unsupervised_thread(self, node: ast.Call) -> None:
        """BDL014: threads under ``bigdl_tpu/serving/`` must be spawned via
        ``serving/resilience.py::spawn_worker`` — the seam that names,
        daemonizes, and makes them restartable/supervisable. A raw
        ``threading.Thread`` is a worker whose silent death hangs every
        caller blocked on one of its futures; the helper's own construction
        carries the one sanctioned suppression."""
        msg = (
            "constructed directly under bigdl_tpu/serving/ bypasses the "
            "supervised spawn seam (serving/resilience.spawn_worker): an "
            "unsupervised worker's silent death hangs every caller blocked "
            "on its futures — spawn through the helper (or suppress with a "
            "reason)"
        )
        func = node.func
        if (
            isinstance(func, ast.Name)
            and func.id in self.aliases.from_threading_thread
        ):
            self._report(node, "BDL014", f"{func.id}() {msg}")
            return
        chain = _attr_chain(func)
        if (
            chain
            and len(chain) == 2
            and chain[0] in self.aliases.threading_mod
            and chain[1] == "Thread"
        ):
            self._report(node, "BDL014", f"threading.Thread() {msg}")

    def _check_unpropagated_context(self, node: ast.Call) -> None:
        """BDL022: in library modules using the causal-tracing seam
        (``obs.trace``), a raw ``threading.Thread`` construction severs the
        trace — thread-local ``TraceContext``/``SpanCollector`` does not
        cross the spawn, so the worker's spans are orphans. Clean when the
        enclosing function (nested thread targets included) hands context
        across itself (``bind_context``/``context_scope``/
        ``bind_collector``) or spawns via ``spawn_worker`` (which captures
        and re-binds the spawner's context); an explicit
        ``spawn_worker(context=None)`` severs deliberately and carries a
        suppression naming why."""
        func = node.func
        name = (
            func.id if isinstance(func, ast.Name)
            else func.attr if isinstance(func, ast.Attribute)
            else None
        )
        if name == "spawn_worker":
            for k in node.keywords:
                if (
                    k.arg == "context"
                    and isinstance(k.value, ast.Constant)
                    and k.value.value is None
                ):
                    self._report(
                        node,
                        "BDL022",
                        "spawn_worker(context=None) explicitly severs the "
                        "causal trace at this seam; drop the argument to "
                        "inherit the spawner's TraceContext, or suppress "
                        "with the reason the chain ends here",
                    )
            return
        is_thread = (
            isinstance(func, ast.Name)
            and func.id in self.aliases.from_threading_thread
        )
        if not is_thread:
            chain = _attr_chain(func)
            is_thread = (
                chain is not None
                and len(chain) == 2
                and chain[0] in self.aliases.threading_mod
                and chain[1] == "Thread"
            )
        if not is_thread:
            return
        if any(self._ctxprop_stack):
            return  # an enclosing function hands context across the seam
        self._report(
            node,
            "BDL022",
            "threading.Thread() in a module using the causal-tracing seam "
            "(obs.trace) severs the active trace: thread-local "
            "TraceContext/SpanCollector does not cross the spawn, so the "
            "worker's spans are orphans — spawn via "
            "serving/resilience.spawn_worker (inherits the context), or "
            "bind_context/context_scope/bind_collector inside the thread "
            "target",
        )

    def _check_unbounded_queue(self, node: ast.Call) -> None:
        """BDL011: in the input-pipeline hot modules, every inter-thread
        queue must carry an explicit bound — an unbounded ``queue.Queue()``
        or ``collections.deque()`` between a producer and a stalled consumer
        grows host memory without limit (decoded batches pin big buffers)."""
        func = node.func
        chain = _attr_chain(func)
        kind = None
        if isinstance(func, ast.Name):
            if func.id in self.aliases.from_queue:
                kind = "simple" if func.id == "SimpleQueue" else "queue"
            elif func.id in self.aliases.from_collections_deque:
                kind = "deque"
        elif chain and len(chain) == 2:
            if chain[0] in self.aliases.queue_mod and chain[1] in (
                "Queue", "LifoQueue", "PriorityQueue", "SimpleQueue",
            ):
                kind = "simple" if chain[1] == "SimpleQueue" else "queue"
            elif (
                chain[0] in self.aliases.collections_mod
                and chain[1] == "deque"
            ):
                kind = "deque"
        if kind is None:
            return

        def unbounded_const(expr) -> bool:
            return isinstance(expr, ast.Constant) and (
                expr.value is None
                or (isinstance(expr.value, int) and expr.value <= 0)
            )

        if kind == "queue":
            bound = node.args[0] if node.args else next(
                (k.value for k in node.keywords if k.arg == "maxsize"), None
            )
            bad = bound is None or unbounded_const(bound)
        elif kind == "deque":
            bound = node.args[1] if len(node.args) >= 2 else next(
                (k.value for k in node.keywords if k.arg == "maxlen"), None
            )
            bad = bound is None or unbounded_const(bound)
        else:  # SimpleQueue has no bound at all
            bad = True
        if bad:
            self._report(
                node,
                "BDL011",
                "unbounded queue in an input-pipeline hot module: a stalled "
                "consumer lets it grow without limit, pinning host memory — "
                "pass an explicit maxsize/maxlen or use "
                "dataset.pipeline.StagingRing (bounded, event-aware close)",
            )

    def _check_raw_pallas_call(self, node: ast.Call,
                               chain: Tuple[str, ...]) -> None:
        """BDL009: in ``bigdl_tpu/``, every kernel launch must route through
        ``utils.compat.pallas_call`` — the interpret-fallback helper that
        resolves ``interpret=None`` per backend (CPU tier-1 runs the real
        kernel programs in interpret mode; a raw ``pl.pallas_call`` dies in
        the Mosaic compiler off-TPU). The helper's own launch carries the
        suppression."""
        is_raw = (
            chain[-1] == "pallas_call"
            and (
                chain[0] in self.aliases.pallas
                or (len(chain) >= 4 and chain[0] in self.aliases.jax
                    and chain[-3:-1] == ("experimental", "pallas"))
            )
        )
        if is_raw:
            self._report(
                node,
                "BDL009",
                f"raw {'.'.join(chain)}() bypasses the interpret fallback; "
                "route kernels through utils.compat.pallas_call so they "
                "degrade to interpret mode off-TPU",
            )

    def _check_raw_collective(self, node: ast.Call,
                              chain: Tuple[str, ...]) -> None:
        """BDL021: in ``bigdl_tpu/`` outside ``parallel/``, ``lax.ppermute``
        / ``lax.all_to_all`` are hand-rolled collective schedules — they
        belong behind the parallel helpers, which own the mesh-axis
        conventions and feed the PerfAccountant comms decomposition."""
        is_raw = chain[-1] in _RAW_COLLECTIVE_NAMES and (
            chain[0] in self.aliases.lax
            or (len(chain) >= 3 and chain[0] in self.aliases.jax
                and chain[-2] == "lax")
        )
        if is_raw:
            self._report(
                node,
                "BDL021",
                f"raw {'.'.join(chain)}() outside bigdl_tpu/parallel/ is a "
                "hand-rolled collective schedule; route it through the "
                "parallel helpers (pipeline_apply / moe_ffn / "
                "ring_attention) so mesh conventions and the perf comms "
                "decomposition stay centralized",
            )

    def _check_process_topology(self, node: ast.Call,
                                chain: Tuple[str, ...]) -> None:
        """BDL023: in ``bigdl_tpu/`` outside ``utils/engine.py`` +
        ``parallel/``, ``jax.distributed.initialize`` and raw jax mesh
        construction (``jax.sharding.Mesh`` / ``jax.make_mesh``) are
        banned — fleet identity enters through ``Engine.init_distributed``
        once, and mesh topology derives from it only in the sanctioned
        seams, so survivors and checkpoints can never disagree on the
        device layout after an elastic shrink/rejoin."""
        if chain[-1] == "initialize" and (
            ("distributed" in chain[:-1] and chain[0] in self.aliases.jax)
            or (len(chain) == 2 and chain[0] in self.aliases.distributed_mod)
        ):
            self._report(
                node,
                "BDL023",
                f"{'.'.join(chain)}() outside Engine.init_distributed; "
                "fleet identity (process_index/process_count) enters through "
                "the one Engine seam so every subsystem agrees on membership",
            )
            return
        is_mesh = (
            chain[-1] == "Mesh"
            and (
                chain[0] in self.aliases.sharding_mod
                or ("sharding" in chain[:-1] and chain[0] in self.aliases.jax)
            )
        ) or (
            chain[-1] == "make_mesh"
            and len(chain) == 2
            and chain[0] in self.aliases.jax
        )
        if is_mesh:
            self._report(
                node,
                "BDL023",
                f"{'.'.join(chain)}() builds a jax mesh outside the "
                "process-topology seams (utils/engine.py + "
                "bigdl_tpu/parallel/); build meshes through Engine.mesh() "
                "or parallel.make_mesh so the topology derived from "
                "process_count stays consistent with the elastic "
                "coordinator's device-block arithmetic",
            )

    _EXIT_OS_MSG = (
        "os._exit() skips every finally/atexit teardown, so the flight "
        "recorder never seals a postmortem bundle and checkpoints can be "
        "left half-written; raise a typed exception (or route hard exits "
        "through the sanctioned seams: obs/blackbox.py, "
        "resilience/preemption.py)"
    )
    _EXIT_SYS_MSG = (
        "bare sys.exit() in library code bypasses the failure-policy "
        "escalation that dumps a postmortem bundle on the way down; raise "
        "a typed exception and let optimize()/ModelServer's handlers seal "
        'the bundle (sys.exit under `if __name__ == "__main__":` stays '
        "free)"
    )
    _EXIT_SIGNAL_MSG = (
        "raw signal.signal() outside the sanctioned handler seams "
        "(obs/blackbox.py faulthandler arming, resilience/preemption.py "
        "SIGTERM guard) can silently replace the crash/preemption hooks "
        "that make every abnormal exit leave a triageable artifact; "
        "register handlers through those seams"
    )

    def _check_exit_bypass(self, node: ast.Call,
                           chain: Tuple[str, ...]) -> None:
        """BDL024: in ``bigdl_tpu/`` outside ``obs/blackbox.py`` +
        ``resilience/preemption.py``, ``os._exit`` / bare ``sys.exit`` /
        ``signal.signal`` are banned — each is a way for a process to die
        (or rewire how it dies) without the flight recorder sealing a
        postmortem bundle. ``sys.exit`` under an
        ``if __name__ == "__main__":`` guard is CLI plumbing and exempt."""
        if len(chain) != 2:
            return
        root, attr = chain
        if root in self.aliases.os_mod and attr == "_exit":
            self._report(node, "BDL024", self._EXIT_OS_MSG)
        elif (
            root in self.aliases.sys_mod
            and attr == "exit"
            and not self._main_guard_depth
        ):
            self._report(node, "BDL024", self._EXIT_SYS_MSG)
        elif root in self.aliases.signal_mod and attr == "signal":
            self._report(node, "BDL024", self._EXIT_SIGNAL_MSG)

    def _check_perf_introspection(self, node: ast.Call,
                                  chain: Tuple[str, ...]) -> None:
        """BDL016: lowered-program cost introspection and jax.profiler
        CAPTURE calls live only in the sanctioned ``obs/profiler.py`` +
        ``obs/perf.py`` seams — a stray ``cost_analysis`` (flagged at the
        attribute level in ``visit_Call``, since it usually chains off a
        call result) compiles programs behind the telemetry layer's back,
        and a raw ``start_trace`` aborts whichever capture window already
        holds the process-wide profiler."""
        if chain[-1] in _PROFILER_CAPTURE_NAMES and (
            # jax.profiler.start_trace(...) through a jax alias
            ("profiler" in chain[:-1] and chain[0] in self.aliases.jax)
            # profiler.start_trace(...) via `from jax import profiler` /
            # jp.start_trace(...) via `import jax.profiler as jp`
            or (len(chain) == 2 and chain[0] in self.aliases.profiler_mod)
        ):
            self._report(
                node,
                "BDL016",
                f"{'.'.join(chain)}() outside the sanctioned obs/perf.py "
                "capture seam; route trace windows through "
                "obs.perf.start_capture/stop_capture so concurrent windows "
                "(set_profile, PerfMonitor breaches) serialize instead of "
                "aborting each other",
            )

    def _check_obs_host_pull(self, node: ast.Call, chain: Tuple[str, ...]) -> None:
        """BDL008: ``bigdl_tpu/obs/`` must not materialize device values —
        ``jax.device_get`` or ``np.asarray``/``np.array`` anywhere in the
        package is a host pull outside the sanctioned one-step-late seam
        (which carries the suppression). ``jnp.asarray`` stays traced and is
        fine."""
        if chain[0] in self.aliases.jax and chain[-1] == "device_get":
            self._report(
                node,
                "BDL008",
                f"{'.'.join(chain)}() in obs code is a device->host pull; "
                "the obs layer adds ZERO host syncs — route the value "
                "through the one-step-late HealthMonitor.snapshot seam",
            )
        elif chain[0] in self.aliases.numpy and chain[-1] in ("asarray", "array"):
            self._report(
                node,
                "BDL008",
                f"{'.'.join(chain)}() in obs code materializes a (possibly "
                "device) value on host; the obs layer adds ZERO host syncs "
                "— use jnp, or the sanctioned snapshot seam",
            )

    def _check_host_sync(self, node: ast.Call, chain: Tuple[str, ...]) -> None:
        if len(chain) == 2 and chain[0] in self.aliases.time and chain[1] in TIME_BANNED:
            self._report(
                node,
                "BDL002",
                f"{'.'.join(chain)}() inside a jitted forward (_apply/_fn) is "
                "a host call: it runs once at trace time, not per step",
            )
        elif chain[-1] == "block_until_ready":
            self._report(
                node,
                "BDL002",
                ".block_until_ready() inside a jitted forward serializes the "
                "device pipeline",
            )
        elif chain[-1] == "item" and not node.args and not node.keywords:
            self._report(
                node,
                "BDL002",
                ".item() inside a jitted forward forces a device->host sync",
            )
        elif len(chain) >= 2 and chain[0] in self.aliases.numpy and chain[-1] in (
            "asarray", "array",
        ):
            self._report(
                node,
                "BDL002",
                f"{'.'.join(chain)}() inside a jitted forward materializes on "
                "host and breaks tracing; use jnp",
            )


# --------------------------------------------------------------------------
# BDL004: shape-contract coverage over the nn class hierarchy
# --------------------------------------------------------------------------

@dataclass
class _ClassInfo:
    name: str
    path: str
    line: int
    bases: Tuple[str, ...]
    has_contract: bool  # infer_shape def/assign in class body
    concrete_apply: bool  # _apply defined with a non-`raise`-only body


class ClassTable:
    """Package-wide class registry resolved purely from ASTs.

    Classes are kept per (path, name) — one bare-name dict would let a
    same-named class in another file (keras wrappers shadow ~30 core layer
    names) overwrite a core entry and silently disable the rule for it.
    Base lookups prefer the same file, then a unique cross-file match.
    """

    def __init__(self):
        self.by_key: Dict[Tuple[str, str], _ClassInfo] = {}
        self.by_name: Dict[str, List[_ClassInfo]] = {}
        # (path, "X") from module-level `X.infer_shape = ...`
        self.module_level_assigns: Set[Tuple[str, str]] = set()

    def collect(self, path: str, tree: ast.AST) -> None:
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                self._collect_class(path, node)
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if (
                        isinstance(t, ast.Attribute)
                        and t.attr == "infer_shape"
                        and isinstance(t.value, ast.Name)
                    ):
                        self.module_level_assigns.add((path, t.value.id))

    def _collect_class(self, path: str, node: ast.ClassDef) -> None:
        has_contract = False
        concrete_apply = False
        for item in node.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if item.name == "infer_shape":
                    has_contract = True
                elif item.name == "_apply":
                    body = [
                        s for s in item.body
                        if not (
                            isinstance(s, ast.Expr)
                            and isinstance(s.value, ast.Constant)
                        )
                    ]
                    concrete_apply = not (
                        len(body) == 1 and isinstance(body[0], ast.Raise)
                    )
            elif isinstance(item, ast.Assign):
                if any(
                    isinstance(t, ast.Name) and t.id == "infer_shape"
                    for t in item.targets
                ):
                    has_contract = True
        bases = tuple(
            b.id if isinstance(b, ast.Name) else b.attr
            for b in node.bases
            if isinstance(b, (ast.Name, ast.Attribute))
        )
        info = _ClassInfo(
            node.name, path, node.lineno, bases, has_contract, concrete_apply
        )
        self.by_key[(path, node.name)] = info
        self.by_name.setdefault(node.name, []).append(info)

    def _lookup(self, from_path: str, name: str) -> Optional[_ClassInfo]:
        same_file = self.by_key.get((from_path, name))
        if same_file is not None:
            return same_file
        candidates = self.by_name.get(name, [])
        return candidates[0] if len(candidates) == 1 else None

    def resolves_contract(
        self, info: _ClassInfo, _seen: Optional[Set[Tuple[str, str]]] = None
    ) -> bool:
        """True if the class or a package ancestor (excluding AbstractModule's
        no-contract default) provides infer_shape."""
        if info.name == "AbstractModule":
            return False
        _seen = _seen or set()
        key = (info.path, info.name)
        if key in _seen:
            return False
        _seen.add(key)
        if info.has_contract or (info.path, info.name) in self.module_level_assigns:
            return True
        for b in info.bases:
            base = self._lookup(info.path, b)
            if base is not None and base.name != "AbstractModule" and self.resolves_contract(
                base, _seen
            ):
                return True
        return False

    def contract_findings(self, src_by_path: Dict[str, str]) -> List[Finding]:
        out: List[Finding] = []
        for info in self.by_key.values():
            parts = info.path.replace(os.sep, "/").split("/")
            in_core = (
                "nn" in parts and parts[-1] in CORE_CONTRACT_FILES
            )
            if not in_core or not info.concrete_apply:
                continue
            if self.resolves_contract(info):
                continue
            lines = src_by_path[info.path].split("\n")
            if _suppressed(lines, info.line, "BDL004"):
                continue
            out.append(
                Finding(
                    info.path,
                    info.line,
                    "BDL004",
                    f"layer class {info.name} defines _apply but exposes no "
                    "infer_shape contract (define one, inherit one, or "
                    "suppress with a reason)",
                )
            )
        return out


# --------------------------------------------------------------------------


def iter_py_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            out.append(p)
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(
                    d for d in dirs if d not in ("__pycache__", ".git")
                )
                out.extend(
                    os.path.join(root, f) for f in sorted(files) if f.endswith(".py")
                )
    return out


_CONCURRENCY_MOD = None


def _concurrency_auditor():
    """Load ``bigdl_tpu/analysis/concurrency.py`` by file path (cached).

    A normal package import would execute ``bigdl_tpu.analysis.__init__``,
    which imports jax — and the lint gate's contract is jax-free, fast,
    pure-AST. The auditor module is itself pure stdlib by design."""
    global _CONCURRENCY_MOD
    if _CONCURRENCY_MOD is None:
        import importlib.util

        p = os.path.normpath(os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "..", "bigdl_tpu", "analysis", "concurrency.py",
        ))
        spec = importlib.util.spec_from_file_location(
            "_bdl_concurrency_audit", p
        )
        assert spec is not None and spec.loader is not None
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod  # dataclasses resolve via sys.modules
        spec.loader.exec_module(mod)
        _CONCURRENCY_MOD = mod
    return _CONCURRENCY_MOD


def lint_paths(paths: Sequence[str]) -> List[Finding]:
    files = iter_py_files(paths)
    findings: List[Finding] = []
    table = ClassTable()
    src_by_path: Dict[str, str] = {}
    trees: Dict[str, ast.AST] = {}
    for f in files:
        with open(f, encoding="utf-8") as fh:
            src = fh.read()
        try:
            tree = ast.parse(src, filename=f)
        except SyntaxError as e:
            findings.append(Finding(f, e.lineno or 1, "BDL000", f"syntax error: {e.msg}"))
            continue
        src_by_path[f] = src
        trees[f] = tree
        table.collect(f, tree)
    for f, tree in trees.items():
        linter = _Linter(f, src_by_path[f], tree)
        linter.visit(tree)
        findings.extend(linter.findings)
    findings.extend(table.contract_findings(src_by_path))
    # BDL017/BDL018/BDL019: the whole-program concurrency auditor over the
    # threaded-subsystem files in scope (it applies the same suppression
    # syntax itself)
    conc = _concurrency_auditor()
    conc_files = conc.scope_filter(files)
    if conc_files:
        findings.extend(
            Finding(f.path, f.line, f.code, f.message)
            for f in conc.audit_paths(conc_files)
        )
    findings.sort(key=lambda x: (x.path, x.line, x.code))
    return findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("paths", nargs="*", default=["bigdl_tpu"], help="files/dirs to lint")
    ap.add_argument("--rules", action="store_true", help="print rule documentation")
    args = ap.parse_args(argv)
    if args.rules:
        print(__doc__)
        return 0
    findings = lint_paths(args.paths or ["bigdl_tpu"])
    for f in findings:
        print(f)
    if findings:
        print(f"\n{len(findings)} finding(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
