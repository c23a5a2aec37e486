"""``ModelServer``: multi-model TPU serving over Predictor + ContinuousBatcher.

This is BigDL's Cluster Serving story (BigDL 2.0, arXiv 2204.01715) rebuilt
TPU-native on the paper's one-compiled-executable inference model: instead of
a Redis queue feeding Flink tasks that each hold a model copy, ONE process
hosts N named models, each as a single compiled XLA executable per shape
bucket (``Predictor`` shape buckets, ≤1 compile per bucket) fed by a
continuous batcher with latency-SLO flush triggers. Registration warms every
bucket shape once through the persistent compile cache
(``Engine.ensure_compilation_cache``) so the first real request never pays a
compile.

Hot-swap: ``update(name, new_model)`` builds + warms the replacement OFF the
serving path (the old version keeps serving through the compile), then swaps
atomically under the batcher's dispatch lock — in-flight batches drain first,
every outstanding future completes on the version that dispatched it, and the
old executable is retained until the last old-version future resolves.

Quantized fast path: a model whose tree contains the quantized zoo twins
(``nn/quantized.py``) is detected and its family ("int8"/"fp8") tagged on
every serve record; ``register(..., quantize=True)`` (or ``"int8"``) converts
a float model into its int8 twin at registration (int8 ``dot_general``/conv
with int32 accumulation), ``quantize="fp8"`` into the float8 tier
(per-output-channel fp8 weights, f32-accumulated — docs/performance.md).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, Optional, Sequence

log = logging.getLogger("bigdl_tpu.serving")

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.telemetry import Telemetry
from ..optim.predictor import Predictor
from .batcher import ContinuousBatcher
from .queue import ServeFuture, ServeRequest
from .resilience import ServingSupervisor

__all__ = ["ModelServer"]


def _quantized_mode(model):
    """``"int8"`` / ``"fp8"`` when the model already holds quantized layers
    (auto-detection — a pre-quantized zoo model is tagged without asking),
    else ``None``."""
    from ..nn.quantized import quantized_mode

    return quantized_mode(model)


def _resolve_and_convert(name: str, model, quantize):
    """The ONE quantize-contract seam shared by register()/_build and
    update(): normalize the requested mode, reject a family mismatch
    against an already-quantized model, convert a float model when asked.
    Returns ``(model, mode_tag)`` where ``mode_tag`` is the detected family
    string or ``False`` (the serve-record tag)."""
    mode = _resolve_quantize(quantize)
    detected = _quantized_mode(model)
    if mode is not None and detected is not None and detected != mode:
        # the caller asked for one numeric family but handed a model
        # already quantized to another — serving it as-is would tag and
        # run a different path than requested, silently
        raise ValueError(
            f"model {name!r}: quantize={mode!r} requested but the model is "
            f"already {detected}-quantized; pass the float model (or "
            f"quantize={detected!r})"
        )
    if mode is not None and detected is None:
        from ..nn.quantized import quantize as _quantize

        model = _quantize(model, dtype=mode)
        detected = mode
    return model, (detected or False)


def _resolve_quantize(quantize):
    """Normalize the ``register(quantize=)`` surface: ``False``/``None`` →
    no conversion, ``True`` → the int8 fast path (back-compat), ``"int8"`` /
    ``"fp8"`` → that family. An fp8 request on a stack without float8
    support fails here with the capability probe's reason — at registration,
    never inside a warmup trace."""
    if quantize is None or quantize is False:
        return None
    if quantize is True:
        return "int8"
    if quantize in ("int8", "fp8"):
        if quantize == "fp8":
            from ..utils.compat import probe_float8

            support = probe_float8()
            if not support.available:
                raise ValueError(
                    "register(quantize='fp8') requires float8 support, "
                    f"which this stack lacks ({support.reason})"
                )
        return quantize
    raise ValueError(
        f"quantize={quantize!r}: expected False, True, 'int8' or 'fp8'"
    )


class _Entry:
    __slots__ = (
        "name", "model", "predictor", "batcher", "version", "quantized",
        "sample", "shape_buckets", "batch_size", "max_batch", "max_delay_ms",
        "max_pending", "flush_trigger", "drift", "drift_every", "warmup_s",
        "warmup_compiles", "warmup_fresh", "aot_modules", "artifacts",
        "deadline_ms", "breaker", "supervise", "bucket_costs",
    )


class ModelServer:
    """Thread-safe multi-model serving runtime (usable as a context manager).

    One shared :class:`~bigdl_tpu.obs.telemetry.Telemetry` stream carries
    every model's records — per-model ``compile`` events (``path:
    "Predictor[<name>]"``), per-flush ``serve`` records, and drift ``warn``
    records — so ``tools/obs_report.py`` renders the whole server from one
    file.
    """

    def __init__(self, telemetry: Optional[Telemetry] = None,
                 supervisor=None, metrics_port: Optional[int] = None):
        # close() tears down only a sink THIS server minted — a caller's
        # telemetry (often shared with a trainer) must outlive the server
        self._owns_telemetry = telemetry is None
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        # worker supervision (docs/serving.md "resilience"): one monitor
        # thread per server restarts dead batching workers and fails wedged
        # ones' pending futures. None -> a default ServingSupervisor wired
        # to this server's telemetry; False -> unsupervised (tests/embeds);
        # or pass a configured ServingSupervisor.
        if supervisor is False:
            self.supervisor: Optional[ServingSupervisor] = None
        elif supervisor is None:
            self.supervisor = ServingSupervisor(telemetry=self.telemetry)
        else:
            self.supervisor = supervisor
        self._entries: Dict[str, _Entry] = {}
        self._lock = threading.RLock()  # hot-lock: serving traffic reads entries under it
        # management operations (register/update/unregister/close) serialize
        # on this lock for their WHOLE duration — builds and warmup compiles
        # included — so concurrent updates cannot mint duplicate versions or
        # corrupt retirement accounting. Serving traffic never takes it.
        self._mgmt_lock = threading.RLock()  # hot-lock: registry mutations serialize here
        self._run_open = False
        # AOT warm-start state (docs/serving.md "fleet cold-start"): the
        # verified bundle this server was seeded from, if any
        self._warm_path: Optional[str] = None
        self._warm_manifest: Optional[Dict[str, Any]] = None
        # per-replica scrape endpoint (obs/export.py): /healthz serves
        # health() — the surface the multi-replica sharder polls remotely —
        # /metrics the Prometheus gauges from this server's telemetry ring.
        # Device-free by construction (BDL015): a scrape never blocks a
        # flush. metrics_port=0 binds an ephemeral port (.metrics_port).
        self._endpoint = None
        if metrics_port is not None:
            from ..obs.export import ObsEndpoint

            self._endpoint = ObsEndpoint(metrics_port)
            self._endpoint.attach_telemetry(self.telemetry)
            self._endpoint.attach_health(self.health)
            self._endpoint.start()

    # ----------------------------------------------------------- lifecycle
    def __enter__(self) -> "ModelServer":
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> None:
        if exc_type is not None and not issubclass(
                exc_type, (KeyboardInterrupt, GeneratorExit)):
            # an exception is escaping the serving runtime: freeze the
            # flight recorder BEFORE close() drains workers and flips the
            # scrape plane dark — the bundle must show the dying state
            try:
                from ..obs import blackbox

                blackbox.dump_postmortem(
                    "server_%s" % exc_type.__name__,
                    telemetry=self.telemetry, error=exc_val,
                )
            except Exception:  # lint: disable=BDL007 the server exception propagates; the dump is best-effort
                pass
        self.close()

    def close(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop every batcher and close the telemetry run (flushes the
        stream for obs_report). ``drain=True`` (default) serves queued
        requests first; ``drain=False`` fails them with the typed
        :class:`~bigdl_tpu.serving.queue.ServerClosed`. Either way a future
        still unresolved once its worker's join ``timeout`` closes — e.g. a
        wedged dispatch mid-drain — is failed typed, never leaked: no
        caller blocked in ``result()`` survives ``close()`` waiting
        forever."""
        with self._mgmt_lock:
            if self._endpoint is not None:
                # the scrape plane goes dark FIRST: a sharder polling
                # /healthz must see connection-refused (unroutable), not a
                # half-closed server still reporting "serving"
                self._endpoint.close()
                self._endpoint = None
            if self.supervisor is not None:
                # stop supervision FIRST: the shutdown below deliberately
                # kills workers, which must not read as crashes to restart
                self.supervisor.stop()
            with self._lock:
                entries = list(self._entries.values())
                self._entries.clear()
            for e in entries:
                if self.supervisor is not None:
                    self.supervisor.unwatch(e.name)
                e.batcher.stop(drain=drain, timeout=timeout)
                if e.drift is not None:
                    # hand the model back uninstrumented — hooks must not
                    # outlive the server that installed them
                    e.drift.release(e.model)
            if self._run_open:
                self.telemetry.run_ended(
                    "serve", models=[e.name for e in entries]
                )
                self._run_open = False
            if self._owns_telemetry:
                # detaches the sink from the process-default scrape
                # endpoint and closes its exporters; a dead server's last
                # serve gauges must not keep being exported forever
                self.telemetry.close()

    def _ensure_run(self) -> None:
        if not self._run_open:
            self.telemetry.run_started("serve", warm_start=self._warm_path)
            self._run_open = True

    # ------------------------------------------------------------ artifacts
    def warm_start(self, path: str) -> Dict[str, Any]:
        """Verify an artifact bundle and seed this process's compile cache
        from it (``utils/aot.py`` contract: manifest + per-file sha256 +
        environment fingerprint; any mismatch raises the typed
        :class:`~bigdl_tpu.utils.aot.ArtifactIncompatible` — nothing is
        half-seeded). Call BEFORE ``register``; later registrations that name
        this bundle (``artifacts=path``) reuse the verification and install
        the serialized per-bucket modules, so warmup replays as compile-cache
        reads: boot-to-ready in seconds, telemetry-provably 0 fresh
        compiles."""
        from ..utils import aot

        with self._mgmt_lock:
            # kind pre-checked so a trainer bundle never half-seeds the cache
            manifest = aot.warm_start(path, kind="serving")
            self._warm_path, self._warm_manifest = path, manifest
            return manifest

    def export_artifacts(self, path: str) -> Dict[str, Any]:
        """Write the AOT artifact bundle for every registered model —
        serialized per-(model, version, bucket) modules + the compile-cache
        harvest + the manifest (written LAST, checkpoint-style). Serving
        continues meanwhile; only management operations are excluded."""
        from . import artifacts as _artifacts

        with self._mgmt_lock:
            return _artifacts.export_server_artifacts(self, path)

    def _export_entries(self):
        with self._lock:
            return list(self._entries.values())

    def _artifact_manifest(self, path: str, name: str):
        """Resolve + verify a bundle for one registration, with the serving
        degrade policy: any :class:`ArtifactIncompatible` is logged, emitted
        as a ``warn`` telemetry record, and turns into ``None`` — the caller
        then registers through ordinary trace+compile. A replica must come up
        serving either way; only its boot latency differs."""
        from ..utils import aot

        if self._warm_path == path and self._warm_manifest is not None:
            return self._warm_manifest
        try:
            manifest = aot.load_bundle(path)
            if manifest.get("kind") != "serving":
                raise aot.ArtifactIncompatible(
                    path,
                    f"bundle kind {manifest.get('kind')!r} is not a serving "
                    "bundle",
                )
            aot.seed_from_bundle(path, manifest)
        except aot.ArtifactIncompatible as e:
            log.warning(
                "model %r: artifact bundle rejected (%s); falling back to "
                "trace mode — the replica boots cold but boots", name,
                e.reason,
            )
            self.telemetry.warn(
                reason="artifact_incompatible", path="serve", model=name,
                bundle=path, detail=e.reason,
            )
            return None
        self._warm_path, self._warm_manifest = path, manifest
        return manifest

    # -------------------------------------------------------- registration
    def register(
        self,
        name: str,
        model,
        *,
        sample_input=None,
        batch_size: Optional[int] = None,
        shape_buckets: Optional[Sequence[int]] = None,
        max_batch: Optional[int] = None,
        max_delay_ms: float = 10.0,
        max_pending: Optional[int] = None,
        flush_trigger=None,
        quantize=False,
        warmup: bool = True,
        drift=None,
        drift_every: int = 32,
        artifacts: Optional[str] = None,
        deadline_ms: Optional[float] = None,
        breaker=None,
        supervise: bool = True,
    ) -> None:
        """Host ``model`` under ``name``.

        ``artifacts`` names an AOT bundle (``export_artifacts`` output): the
        bundle is verified + seeded (reusing a prior ``warm_start(path)``
        verification when given the same path), this model's serialized
        per-bucket modules are installed on the predictor, and the warmup
        replay then hits the persistent compile cache — telemetry's
        ``warmup`` record proves 0 fresh compiles. An incompatible/corrupt
        bundle degrades to ordinary trace mode with a logged reason and a
        ``warn`` record, never a dead replica.

        ``sample_input`` is ONE record (no batch dim); required when the
        model is unbuilt or ``warmup=True`` (it defines the record's trailing
        shape/dtype for the warmup drives). ``quantize=True`` (or ``"int8"``)
        converts the model to its int8 zoo twin first; ``quantize="fp8"``
        selects the float8 tier (per-output-channel fp8 weights,
        f32-accumulated ``dot_general`` — docs/performance.md). The mode
        tags every serve record (``quantized: "int8" | "fp8" | false``). ``drift=True`` (or an
        :class:`~bigdl_tpu.obs.health.ActivationDrift`) installs activation
        forward hooks and samples drift every ``drift_every`` batches.
        ``max_pending`` arms per-model admission control: a submit against a
        full queue raises
        :class:`~bigdl_tpu.serving.queue.AdmissionRejected` on the caller's
        thread, and the cumulative ``rejected`` count rides every serve
        record (backpressure instead of unbounded queueing latency).

        Resilience knobs (docs/serving.md "resilience"): ``deadline_ms``
        sets the model's default request deadline — an expired request fails
        with the typed ``DeadlineExceeded`` at the next
        admission/sweep/flush/materialize seam instead of padding a batch or
        blocking its caller (``infer(..., deadline_ms=...)`` overrides per
        request). ``breaker`` configures the per-model circuit breaker
        (``None`` = :class:`~bigdl_tpu.serving.resilience.BreakerConfig`
        defaults, ``False`` = off): consecutive flush failures or a
        deadline-miss rate trip it open, open submits shed with the typed
        ``CircuitOpen`` — siblings on the same server are unaffected.
        ``supervise=False`` opts this model out of the server's
        :class:`~bigdl_tpu.serving.resilience.ServingSupervisor`
        (dead-worker restart + wedge detection).
        """
        with self._mgmt_lock:
            with self._lock:
                if name in self._entries:
                    raise ValueError(
                        f"model {name!r} already registered; use update() to "
                        "hot-swap a new version"
                    )
            self._ensure_run()
            e = _Entry()
            e.name = name
            e.sample = (
                # held-by-design: register() serializes on _mgmt_lock for its
                # WHOLE duration, warmup compiles included (see the lock's
                # decl comment) — serving traffic never contends on it, so a
                # host-side copy of the caller's sample cannot stall serving
                None if sample_input is None
                else np.asarray(sample_input)  # lint: disable=BDL018
            )
            e.shape_buckets = (
                tuple(int(b) for b in shape_buckets) if shape_buckets else None
            )
            e.batch_size = batch_size
            e.max_batch = max_batch
            e.max_delay_ms = max_delay_ms
            e.max_pending = (
                None if max_pending is None else int(max_pending)
            )
            e.flush_trigger = flush_trigger
            e.drift_every = drift_every
            e.drift = self._resolve_drift(drift)
            e.artifacts = artifacts
            e.deadline_ms = deadline_ms
            e.breaker = breaker
            e.supervise = bool(supervise)
            manifest = (
                self._artifact_manifest(artifacts, name)
                if artifacts is not None else None
            )
            self._build(e, model, version=1, quantize=quantize, warmup=warmup,
                        manifest=manifest)
            if warmup is False:
                # satellite fix: a model registered warmup=False silently
                # leaves the FIRST request to pay the compile — surface it in
                # the stream, not just the log, so obs_report can flag it
                log.warning(
                    "model %r registered with warmup=False; the first "
                    "request per shape will pay the compile", name,
                )
                self.telemetry.warn(
                    reason="unwarmed_model", path="serve", model=name,
                )
            with self._lock:
                self._entries[name] = e
            e.batcher.start()
            if e.supervise and self.supervisor is not None:
                self.supervisor.watch(name, e.batcher)
                self.supervisor.start()

    def _resolve_drift(self, drift):
        if drift is None or drift is False:
            return None
        if drift is True:
            from ..obs.health import ActivationDrift

            return ActivationDrift()
        return drift

    def _build(self, e: _Entry, model, *, version: int, quantize,
               warmup: bool, manifest: Optional[Dict[str, Any]] = None) -> None:
        """Build (quantize → ensure-built → predictor → [AOT install] →
        warmup → batcher) one model version into ``e`` — shared by
        register() and update()."""
        if not model.is_built():
            if e.sample is None:
                raise ValueError(
                    f"model {e.name!r} is unbuilt and no sample_input was "
                    "given; pass one record so the server can build + warm it"
                )
            self._ensure_built(e, model)
        model, tag = _resolve_and_convert(e.name, model, quantize)
        e.model = model
        # the serve-record tag: the detected family string, or False — a
        # truthy mode keeps the legacy boolean consumers working
        e.quantized = tag
        e.version = version
        predictor = Predictor(
            model,
            e.batch_size,
            e.shape_buckets,
            telemetry=self.telemetry,
            name=e.name,
            capture_state=e.drift is not None,
        )
        e.aot_modules = (
            self._install_artifacts(e, predictor, manifest)
            if manifest is not None else 0
        )
        e.warmup_s, e.warmup_compiles, e.warmup_fresh = 0.0, 0, None
        if e.drift is not None:
            e.drift.install(model)
        try:
            e.warmup_s = self._warmup(e, predictor) if warmup else 0.0
            # per-bucket serving cost table (obs/perf.py): derived HERE,
            # once per (version, geometry) — the batching thread then stamps
            # serve records with plain arithmetic (BDL010 stays clean)
            e.bucket_costs = self._bucket_costs(e, predictor)
            batcher = ContinuousBatcher(
                predictor,
                name=e.name,
                version=version,
                max_batch=e.max_batch,
                max_delay_ms=e.max_delay_ms,
                max_pending=e.max_pending,
                deadline_ms=e.deadline_ms,
                breaker=e.breaker,
                # heartbeats must live in the supervisor's clock domain —
                # a custom-clock supervisor over default-clock workers
                # would mis-age every beat
                clock=(
                    self.supervisor.clock
                    if self.supervisor is not None else time.monotonic
                ),
                flush_trigger=e.flush_trigger,
                telemetry=self.telemetry,
                drift=e.drift,
                drift_every=e.drift_every,
                tags={"quantized": e.quantized},
                bucket_costs=e.bucket_costs,
            )
        except Exception:
            # rejected registration (warmup failure, bad batcher config):
            # unhook the model again — same no-leak contract as update()
            if e.drift is not None:
                e.drift.release(model)
            raise
        e.predictor = predictor
        e.batcher = batcher

    def _bucket_costs(self, e: _Entry, predictor: Predictor):
        """Per-bucket serving cost table
        (:func:`~bigdl_tpu.obs.perf.predictor_bucket_costs`): the padded-
        batch program flops per bucket, the per-record share, and the peak
        denominator — so each flush's serve record carries achieved
        throughput vs bucket cost. None-graceful: no sample (shape
        unknowable) or a backend without a cost model drops the stamps,
        never the registration."""
        if e.sample is None:
            return None
        import gc

        from ..obs import perf as obs_perf

        try:
            return obs_perf.predictor_bucket_costs(
                predictor, e.sample, e.shape_buckets
            ) or None
        except Exception:
            log.exception(
                "bucket cost derivation for model %r failed; serve records "
                "carry no cost fields", e.name,
            )
            return None
        finally:
            # the per-bucket lowering leaves a pile of trace-time cycles;
            # collected organically, they land inside the NEXT model's TIMED
            # warmup window (warmup seconds are an SLO-locked headline — the
            # ≥10x artifact warm-boot speedup). Collect at this management
            # boundary instead: registration is not a fit, so the optimizer
            # gc-guard's mid-fit hazard does not apply here.
            gc.collect()

    def _ensure_built(self, e: _Entry, model) -> None:
        shape = (
            ((e.shape_buckets[0],) + e.sample.shape[1:])
            if e.shape_buckets
            else e.sample.shape
        )
        model._ensure_built(jnp.asarray(np.zeros((1,) + shape, e.sample.dtype)))

    def _install_artifacts(self, e: _Entry, predictor: Predictor,
                           manifest: Dict[str, Any]) -> int:
        """Install this model's serialized modules from the verified bundle
        onto the predictor's AOT seam. Geometry drift / corrupt module →
        logged ``warn`` + trace-mode fallback (returns 0); the manifest was
        already hash-verified, so this is the per-model half of the
        verify-on-load contract."""
        from ..utils import aot
        from . import artifacts as _artifacts

        bundle = e.artifacts or self._warm_path or "<bundle>"
        try:
            if e.sample is None:
                raise aot.ArtifactIncompatible(
                    bundle,
                    f"model {e.name!r} registered without sample_input — no "
                    "geometry to match the bundle against",
                )
            entry = _artifacts.model_entry(bundle, manifest, e.name)
            _artifacts.check_geometry(
                bundle, entry, e.name,
                batch_size=predictor.batch_size,
                shape_buckets=e.shape_buckets,
                sample=e.sample,
                capture_state=e.drift is not None,
            )
            return _artifacts.install_modules(
                bundle, manifest, entry, predictor, e.sample, e.shape_buckets
            )
        except aot.ArtifactIncompatible as exc:
            log.warning(
                "model %r: artifacts unusable (%s); falling back to trace "
                "mode", e.name, exc.reason,
            )
            self.telemetry.warn(
                reason="artifact_incompatible", path="serve", model=e.name,
                bundle=bundle, detail=exc.reason,
            )
            return 0

    def _warmup(self, e: _Entry, predictor: Predictor,
                version: Optional[int] = None) -> float:
        """Drive every bucket shape once so each executable compiles NOW —
        served from the persistent compile cache when a
        previous process (or a mounted artifact bundle) warmed it — instead
        of on the first user request. Emits one ``warmup`` telemetry record:
        wall seconds, traced-compile count, and — the cold-start headline —
        how many compiles wrote FRESH cache entries (0 on a warm boot).

        Attribution caveat: the compile counter and the cache-dir watch are
        process-wide, and OTHER models keep serving while this one warms
        (only the mgmt lock is held). A concurrent first-per-shape compile
        on another model lands in this model's warmup deltas — the error is
        conservative (a warm boot may read fresh>0, never the reverse), and
        a boot sequence that registers before taking traffic (the normal
        replica flow, and every test) is exact."""
        from ..utils.compat import CacheDirWatch

        if e.sample is None:
            # a built model registered without sample_input: nothing defines
            # the record shape, so the first REAL request pays the compile
            log.warning(
                "model %r registered without sample_input — skipping warmup; "
                "the first request per shape will pay the compile",
                e.name,
            )
            self.telemetry.warn(
                reason="unwarmed_model", path="serve", model=e.name,
            )
            return 0.0
        watch = CacheDirWatch()
        compiles_before = self.telemetry.compile_count
        t0 = time.perf_counter()
        if e.shape_buckets:
            for b in e.shape_buckets:
                x = np.zeros((1, b) + e.sample.shape[1:], e.sample.dtype)
                predictor.forward_batch(x)
        else:
            predictor.forward_batch(np.zeros((1,) + e.sample.shape,
                                             e.sample.dtype))
        warmup_s = time.perf_counter() - t0
        e.warmup_compiles = self.telemetry.compile_count - compiles_before
        # fresh_count (not raw delta): "0 fresh" must read unknowable, not
        # clean, on a jax whose thresholds may skip persisting fast compiles
        e.warmup_fresh = watch.fresh_count()
        self.telemetry.warmup(
            model=e.name,
            seconds=warmup_s,
            compiles=e.warmup_compiles,
            fresh_compiles=e.warmup_fresh,
            warm_start=bool(predictor.aot_coverage()),
            buckets=(list(e.shape_buckets) if e.shape_buckets else None),
            version=e.version if version is None else version,
        )
        return warmup_s

    # ------------------------------------------------------------ hot swap
    def update(self, name: str, new_model, *, quantize=False,
               warmup: bool = True) -> int:
        """Hot-swap ``name`` to ``new_model``; returns the new version.

        The new version is built and warmed while the OLD version keeps
        serving; the swap itself drains the in-flight batch under the
        dispatch lock and is atomic — every future resolves on exactly one
        version's executable, and the old executable is retained until its
        last outstanding future resolves."""
        with self._mgmt_lock:
            e = self._entry(name)
            old_model = e.model
            version = e.version + 1
            if not new_model.is_built():
                if e.sample is None:
                    raise ValueError(
                        f"update({name!r}) with an unbuilt model needs the "
                        "sample_input the original registration provided"
                    )
                self._ensure_built(e, new_model)
            new_model, quantized = _resolve_and_convert(
                name, new_model, quantize
            )
            predictor = Predictor(
                new_model,
                e.predictor.batch_size,  # geometry must match queued requests
                e.shape_buckets,
                telemetry=self.telemetry,
                name=e.name,
                capture_state=e.drift is not None,
            )
            if e.predictor._aot and self._apply_geometry(
                e.model
            ) == self._apply_geometry(new_model) and quantized == e.quantized:
                # the serialized AOT modules take params AND state as
                # ARGUMENTS, so a same-architecture hot-swap keeps
                # dispatching through the already-compiled wrappers — the
                # new version warms without a single trace of the python
                # model. Any structure/shape change in EITHER tree (params
                # or model state — a stats-only layer changes state alone)
                # or an int8 twin gets fresh executables instead: the old
                # program would reject (or silently mis-plumb) the new tree.
                predictor._aot.update(e.predictor._aot)
                # carry the compile-introspection watermarks WITH the fns:
                # the inherited wrappers' jit caches are already populated,
                # and a zeroed watermark would emit a phantom compile record
                # (cache_hit=true) on the swap warmup's first dispatch
                for fn in predictor._aot.values():
                    predictor._fns_seen[id(fn)] = (
                        e.predictor._fns_seen.get(id(fn), 0)
                    )
            if e.drift is not None:
                # hooks go onto the NEW model only; the old version keeps its
                # hooks (it is still serving through the warmup compile) and
                # is released right after the swap retires it
                e.drift.install(new_model)
            prior_warmup = (e.warmup_s, e.warmup_compiles, e.warmup_fresh)
            try:
                if warmup:
                    # rebind warmup_s too: models() must describe ONE
                    # version's boot, not v1's wall next to v2's counts
                    e.warmup_s = self._warmup(e, predictor, version=version)
                e.batcher.swap(predictor, version)
            except Exception:
                # rejected update: unhook the model we just installed on, or
                # every failed update leaks one pinned model in the monitor —
                # and restore the warmup accounting, which _warmup mutated
                # for a version that never installed
                e.warmup_s, e.warmup_compiles, e.warmup_fresh = prior_warmup
                if e.drift is not None and new_model is not old_model:
                    e.drift.release(new_model)
                raise
            e.batcher.tags["quantized"] = quantized
            # re-derive the bucket cost table for the swapped version (same
            # geometry, possibly different architecture → different flops)
            e.bucket_costs = self._bucket_costs(e, predictor)
            e.batcher.bucket_costs = dict(e.bucket_costs or {})
            if e.drift is not None and old_model is not new_model:
                e.drift.release(old_model)
            e.model, e.predictor = new_model, predictor
            e.version, e.quantized = version, quantized
            e.aot_modules = predictor.aot_coverage()
            return version

    @staticmethod
    def _apply_geometry(model):
        """Shape/dtype signature of BOTH trees the exported programs take as
        arguments — params and model state. The AOT carry-over on hot-swap
        keys on this; comparing params alone would hand a state-different
        model (e.g. an added stats-only layer) a wrapper whose state pytree
        no longer matches."""
        return jax.tree_util.tree_map(
            lambda a: (tuple(a.shape), str(a.dtype)),
            (model.get_parameters(), model.get_state()),
        )

    def unregister(self, name: str) -> None:
        with self._mgmt_lock:
            with self._lock:
                e = self._entries.pop(name, None)
            if e is None:
                raise KeyError(f"no model registered as {name!r}")
            if self.supervisor is not None:
                # unwatch BEFORE the stop: the worker's deliberate death
                # must not be diagnosed as a crash and restarted
                self.supervisor.unwatch(name)
            e.batcher.stop(drain=True)
            if e.drift is not None:
                e.drift.release(e.model)

    # ------------------------------------------------------------- serving
    def _entry(self, name: str) -> _Entry:
        with self._lock:
            e = self._entries.get(name)
        if e is None:
            raise KeyError(f"no model registered as {name!r}")
        return e

    def infer(self, name: str, record,
              deadline_ms: Optional[float] = None) -> ServeFuture:
        """Submit ONE record (no batch dim); returns its future. The record
        is converted/bucket-classified on the CALLING thread — the batching
        thread only pads and stacks. ``deadline_ms`` arms a per-request
        deadline overriding the model's registered default: an expired
        request fails with the typed ``DeadlineExceeded`` instead of padding
        a batch or blocking its caller."""
        e = self._entry(name)
        feat = np.asarray(record)
        bucket = (
            e.predictor.bucket_of(feat.shape[0]) if e.shape_buckets else None
        )
        return e.batcher.submit(
            ServeRequest(feat, bucket, deadline_ms=deadline_ms)
        )

    def predict(self, name: str, records) -> np.ndarray:
        """Blocking convenience: submit every record, gather in caller
        order, stack. Mirrors ``Predictor.predict`` over single records —
        bit-identical to it, since both pad to the same bucket/batch
        geometry and run the same compiled program."""
        futs = [self.infer(name, r) for r in records]
        rows = [f.result() for f in futs]
        if rows and isinstance(rows[0], (dict, list, tuple)):
            leaves = [jax.tree_util.tree_leaves(r) for r in rows]
            treedef = jax.tree_util.tree_structure(rows[0])
            stacked = [
                np.stack([l[i] for l in leaves])
                for i in range(len(leaves[0]))
            ]
            return jax.tree_util.tree_unflatten(treedef, stacked)
        return np.stack(rows)

    # ---------------------------------------------------------------- info
    @property
    def metrics_port(self) -> Optional[int]:
        """Bound port of this replica's scrape endpoint (None when
        constructed without ``metrics_port=``)."""
        return None if self._endpoint is None else self._endpoint.port

    def health(self) -> Dict[str, Dict[str, Any]]:
        """Per-model readiness/liveness surface (docs/serving.md): worker
        state (``serving`` / ``open`` / ``probing`` / ``down`` / ``failed``
        / ``stopped``), breaker snapshot, queue depth, last-flush and
        heartbeat ages, restart count, and the cumulative resilience
        counters. This is the contract the future multi-replica
        request-stream sharder polls: a replica whose models read
        ``serving`` is routable; ``open``/``down``/``failed`` models are
        shed at the sharder instead of timing out at the caller."""
        with self._lock:
            entries = dict(self._entries)
        return {name: e.batcher.health_snapshot()
                for name, e in entries.items()}

    def models(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            entries = dict(self._entries)
        out: Dict[str, Dict[str, Any]] = {}
        for name, e in entries.items():
            out[name] = {
                "version": e.version,
                "quantized": e.quantized,
                "batch_size": e.predictor.batch_size,
                "max_batch": e.batcher.max_batch,
                "max_delay_ms": e.max_delay_ms,
                "shape_buckets": e.shape_buckets,
                "max_pending": e.max_pending,
                "queue_depth": e.batcher.queue.depth(),
                "completed": e.batcher.stats.completed,
                "rejected": e.batcher.rejected(),
                "warmup_s": round(e.warmup_s, 6),
                "warmup_compiles": e.warmup_compiles,
                "warmup_fresh_compiles": e.warmup_fresh,
                "aot_modules": e.aot_modules,
                "retired_versions": e.batcher.retired_versions(),
                "deadline_ms": e.deadline_ms,
                "restarts": e.batcher.restarts,
            }
        return out
