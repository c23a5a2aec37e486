"""Inference: ``Predictor``, ``Evaluator``, ``PredictionService``.

Reference behavior (SURVEY.md §3.4): ``$DL/optim/Predictor.scala`` broadcasts the
model to executors and runs batched forward per partition (``model.predict(rdd)``,
``predictClass``); ``$DL/optim/Evaluator.scala`` does the same then folds each
``ValidationMethod``'s per-partition results with ``+``; ``LocalPredictor`` is the
single-JVM path; ``$DL/optim/PredictionService.scala`` is a thread-safe serving
wrapper over an instance pool.

TPU-native design: there is nothing to broadcast — the model's pure apply is
jit-compiled ONCE and reused for every batch (the north-star "Model.predict /
Evaluator reuse the same jit-compiled graph"). Batches are padded to a fixed
shape so every call hits the same executable (no retrace), and when the Engine
mesh has multiple devices the padded batch is sharded over the ``data`` axis so
prediction scales exactly like training. The instance pool collapses to one
compiled executable: XLA executables are thread-safe, so ``PredictionService``
is a lock around host-side state only.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..dataset.dataset import AbstractDataSet, MiniBatch, Sample, pad_minibatch
from ..obs import trace as obs_trace
from ..obs.trace import span as obs_span
from ..utils.engine import Engine
from .validation import ValidationMethod, ValidationResult

_tm = jax.tree_util.tree_map


def _pad_batch(x, n: int, total: int):
    """Pad leading dim from n to total by repeating row 0 (masked out later)."""
    if n == total:
        return x

    def pad_leaf(a):
        pad = jnp.broadcast_to(a[:1], (total - n,) + a.shape[1:])
        return jnp.concatenate([a, pad], axis=0)

    return _tm(pad_leaf, x)


def _leading_dim(x) -> int:
    leaves = jax.tree_util.tree_leaves(x)
    return int(leaves[0].shape[0])


class Predictor:
    """Batched inference reusing one jit-compiled apply (reference: Predictor /
    LocalPredictor, $DL/optim/Predictor.scala, $DL/optim/LocalPredictor.scala).

    ``shape_buckets`` kills the other retrace source — variable-LENGTH records
    (token sequences): each record is zero-padded up to the smallest bucket
    boundary that fits it and records are batched per bucket, so a sweep over
    mixed-size inputs compiles at most once per bucket instead of once per
    distinct length. Pad id 0 follows the framework's masking convention
    (``BucketedTextDataSet`` / ``Transformer(pad_masking=...)``): models that
    mask pads give exact results; for others the pads are visible input, the
    same contract as the bucketed dataset."""

    def __init__(self, model, batch_size: Optional[int] = None,
                 shape_buckets: Optional[Sequence[int]] = None,
                 telemetry=None, name: Optional[str] = None,
                 capture_state: bool = False):
        self.model = model
        # obs.Telemetry sink: one "step" record per forward dispatch plus
        # compile events off the jit-cache delta (docs/observability.md).
        # wall_s covers pad+dispatch only and records_per_sec stays None —
        # dispatch is async; the sync happens when the caller materializes
        # outputs, so no honest throughput exists inside this window.
        self.telemetry = telemetry
        # `name` tags this predictor's telemetry records (the ModelServer
        # hosts several predictors on ONE stream — per-(model, bucket)
        # compile accounting needs the records to say whose they are)
        self.name = name
        self._tel_path = f"Predictor[{name}]" if name else "Predictor"
        # capture_state=True makes the compiled apply also return the new
        # model state and stashes it (still on device — no sync) as
        # ``.last_state``; the serving layer's activation-drift monitor reads
        # its forward-hook statistics out of it at its sampling stride.
        self.capture_state = capture_state
        self.last_state = None
        self._predict_calls = 0
        # per-dispatch-fn jit-cache watermarks: the AOT seam below can route
        # different padded shapes through different compiled callables, and
        # each needs its own compile-count introspection
        self._fns_seen: Dict[int, int] = {}
        # AOT fast path (utils/aot.py): padded-input-shape key -> jitted
        # deserialized jax.export module. A warm-started replica dispatches
        # through these instead of re-tracing the python model — the warmup
        # "compile" is then a thin-wrapper trace + a persistent-cache read.
        self._aot: Dict[tuple, Any] = {}
        self._cache_watch = None  # lazy CacheDirWatch (first compile observed)
        Engine.ensure_compilation_cache()  # persistent XLA compile cache
        mesh = Engine.mesh() if Engine.is_initialized() else None
        self._n_dev = int(mesh.devices.size) if mesh is not None else 1
        if batch_size is None:
            batch_size = 32 * self._n_dev
        if batch_size % self._n_dev != 0:
            raise ValueError(
                f"batch_size {batch_size} not divisible by {self._n_dev} devices"
            )
        self.batch_size = int(batch_size)
        if shape_buckets is not None:
            b = [int(x) for x in shape_buckets]
            if not b or b != sorted(set(b)):
                raise ValueError(
                    f"shape_buckets must be ascending and unique, got {shape_buckets}"
                )
            shape_buckets = tuple(b)
        self.shape_buckets = shape_buckets
        self._sharding = (
            NamedSharding(mesh, P(mesh.axis_names[0])) if self._n_dev > 1 else None
        )
        self._fn = None

    def _compiled(self):
        if self._fn is None:
            model = self.model
            capture = self.capture_state

            def f(params, state, x):
                y, new_state = model.apply(
                    params, state, x, training=False, rng=None
                )
                return (y, new_state) if capture else y

            self._fn = jax.jit(f)
        return self._fn

    # ------------------------------------------------------------ AOT seam
    @staticmethod
    def aot_key(x) -> tuple:
        """Shape/dtype signature of a padded input batch — the key AOT
        modules are installed and looked up under (one serialized module per
        compiled input geometry, mirroring one executable per bucket)."""
        return tuple(
            (tuple(a.shape), str(a.dtype))
            for a in jax.tree_util.tree_leaves(x)
        )

    def install_aot_call(self, key: tuple, exported) -> None:
        """Route the padded input geometry ``key`` through a deserialized
        ``jax.export`` module (``utils/aot.py`` bundle payload): dispatches
        replay the exporter's lowered program — same (params, state, x)
        calling convention — without re-tracing the python model, and the
        single wrapper compile is a persistent-cache read on a seeded host.
        The traced path remains the fallback for uncovered geometries."""
        self._aot[key] = jax.jit(exported.call)

    def aot_coverage(self) -> int:
        return len(self._aot)

    def _dispatch_fn(self, xp):
        if self._aot:
            fn = self._aot.get(self.aot_key(xp))
            if fn is not None:
                return fn
        return self._compiled()

    def _forward_padded(self, x):
        n = _leading_dim(x)
        if n > self.batch_size:
            raise ValueError(
                f"batch of {n} rows exceeds the predictor's fixed batch_size "
                f"{self.batch_size}"
            )
        t0 = time.perf_counter()
        with obs_span("pad_mask"):
            xp = _pad_batch(_tm(jnp.asarray, x), n, self.batch_size)
            if self._sharding is not None:
                xp = _tm(lambda a: jax.device_put(a, self._sharding), xp)
        fn = self._dispatch_fn(xp)
        if self.telemetry is not None and self._cache_watch is None:
            # snapshot the persistent cache BEFORE the dispatch that may
            # compile — a watch created after the fact would classify the
            # first (cold) compile's own entries as pre-existing
            from ..utils.compat import CacheDirWatch

            self._cache_watch = CacheDirWatch()
        with obs_trace.step_annotation(self._predict_calls):
            y = fn(self.model.get_parameters(), self.model.get_state(), xp)
        if self.capture_state:
            y, self.last_state = y  # device tree kept lazy — no host sync
        wall = time.perf_counter() - t0
        if self.telemetry is not None:
            from ..obs.telemetry import observe_jit_compiles

            obs_trace.add_sample("dispatch", wall)
            self._fns_seen[id(fn)] = observe_jit_compiles(
                fn, self._fns_seen.get(id(fn), 0), self.telemetry,
                iteration=self._predict_calls, seconds=wall,
                path=self._tel_path, cache_watch=self._cache_watch,
            )
            # no records_per_sec: dispatch is async, so a rate built on it
            # would read ~1000x real throughput on TPU — the sync happens
            # when the caller materializes outputs, outside this window
            self.telemetry.step(
                path=self._tel_path,
                iteration=self._predict_calls,
                records=n,
                wall_s=wall,
                dispatch_s=wall,
            )
        self._predict_calls += 1
        return _tm(lambda a: a[:n], y)

    def forward_batch(self, x):
        """Public dispatch seam for the serving layer: forward one host batch
        of AT MOST ``batch_size`` rows through the single compiled executable
        (padded up to the fixed shape, sharded over the mesh) and return the
        outputs sliced back to the real row count — still DEVICE arrays, so
        the caller decides where the materialization sync happens (the
        continuous batcher resolves per-request futures with row views and
        the requesting thread materializes its own slice)."""
        if not self.model.is_built():  # cold path: first flush, unwarmed model
            self.model._ensure_built(_tm(jnp.asarray, x))
        return self._forward_padded(x)

    def _iter_inputs(self, data):
        """Yield input chunks of AT MOST ``batch_size`` rows over a DataSet /
        array / list of Samples (dataset batches are re-chunked so every jit call
        sees the predictor's fixed shape)."""
        bs = self.batch_size
        if isinstance(data, AbstractDataSet):
            for batch in data.data(train=False):
                x = batch.get_input()
                n = batch.size()
                for i in range(0, n, bs):
                    yield _tm(lambda a: a[i : i + bs], x)
        elif isinstance(data, (list, tuple)) and data and isinstance(data[0], Sample):
            for i in range(0, len(data), bs):
                yield np.stack([np.asarray(s.feature) for s in data[i : i + bs]])
        else:
            arr = np.asarray(data)
            for i in range(0, arr.shape[0], bs):
                yield arr[i : i + bs]

    # ----------------------------------------------------- shape bucketing
    @staticmethod
    def _ragged_features(data) -> Optional[List[np.ndarray]]:
        """Features of a list/tuple of Samples or arrays whose leading dims
        differ (the mixed-size case shape bucketing exists for), else None."""
        if not isinstance(data, (list, tuple)) or not data:
            return None
        feats = []
        for s in data:
            a = np.asarray(s.feature if isinstance(s, Sample) else s)
            if a.ndim < 1:
                return None
            feats.append(a)
        if len({f.shape[0] for f in feats}) <= 1:
            return None  # uniform lengths: the ordinary fixed-shape path
        return feats

    def bucket_of(self, length: int) -> int:
        """Smallest shape bucket that fits a length-``length`` record — the
        admission rule shared by :meth:`_predict_bucketed` and the serving
        batcher (which groups single-record requests by this boundary)."""
        if self.shape_buckets is None:
            raise ValueError("predictor has no shape_buckets")
        for b in self.shape_buckets:
            if length <= b:
                return b
        raise ValueError(
            f"record length {length} > largest shape bucket "
            f"{self.shape_buckets[-1]}; extend shape_buckets"
        )

    @staticmethod
    def pad_record(feat: np.ndarray, bucket: int) -> np.ndarray:
        """Zero-pad one record's leading dim up to ``bucket`` (pad id 0, the
        framework's masking convention) — shared with the serving batcher."""
        return np.pad(
            feat,
            [(0, bucket - feat.shape[0])] + [(0, 0)] * (feat.ndim - 1),
        )

    def _predict_bucketed(self, feats: List[np.ndarray]) -> np.ndarray:
        """Pad each record to its bucket boundary, batch per bucket, restore
        the caller's record order. One compile per bucket actually used."""
        buckets: Dict[int, List[int]] = {}
        for i, f in enumerate(feats):
            try:
                buckets.setdefault(self.bucket_of(f.shape[0]), []).append(i)
            except ValueError as e:
                raise ValueError(f"record {i}: {e}") from None
        out: List[Any] = [None] * len(feats)
        bs = self.batch_size
        for b in sorted(buckets):
            idx = buckets[b]
            padded = np.stack([self.pad_record(feats[i], b) for i in idx])
            self.model._ensure_built(jnp.asarray(padded[:1]))
            for s in range(0, len(idx), bs):
                y = _tm(np.asarray, self._forward_padded(padded[s:s + bs]))
                for row, i in enumerate(idx[s:s + bs]):
                    out[i] = _tm(lambda a: a[row], y)
        try:
            leaves = [jax.tree_util.tree_leaves(o) for o in out]
            treedef = jax.tree_util.tree_structure(out[0])
            stacked = [np.stack([l[i] for l in leaves])
                       for i in range(len(leaves[0]))]
        except ValueError as e:
            raise ValueError(
                "bucketed predict outputs differ in shape across buckets — "
                "shape_buckets needs a model whose per-record output shape "
                "is length-independent (e.g. a pooled classifier head)"
            ) from e
        return jax.tree_util.tree_unflatten(treedef, stacked)

    def predict(self, data) -> np.ndarray:
        """Forward every record; returns stacked outputs (reference returns
        RDD[Activity] — here a single host array / pytree of arrays)."""
        if self.telemetry is None:
            return self._predict_impl(data)
        # one predict() sweep = one telemetry run (meta records bound it,
        # spans collect, the watchdog — if any — is armed for the sweep)
        self.telemetry.run_started("Predictor")
        try:
            return self._predict_impl(data)
        finally:
            self.telemetry.run_ended("Predictor")

    def _predict_impl(self, data) -> np.ndarray:
        if self.shape_buckets is not None:
            feats = self._ragged_features(data)
            if feats is not None:
                return self._predict_bucketed(feats)
        chunks = self._iter_inputs(data)
        first = next(chunks, None)
        if first is None:
            return self._empty_output(data)
        self.model._ensure_built(_tm(jnp.asarray, first))
        outs: List[Any] = []
        for x in itertools.chain([first], chunks):
            outs.append(_tm(np.asarray, self._forward_padded(x)))
        if isinstance(outs[0], (dict, list, tuple)):
            flat = [jax.tree_util.tree_leaves(o) for o in outs]
            treedef = jax.tree_util.tree_structure(outs[0])
            stacked = [np.concatenate([f[i] for f in flat]) for i in range(len(flat[0]))]
            return jax.tree_util.tree_unflatten(treedef, stacked)
        return np.concatenate(outs, axis=0)

    def _empty_output(self, data):
        """Empty sweep: shape the empty result by the model's OUTPUT spec via
        ``jax.eval_shape`` so it keeps the real rank/dtype/pytree structure —
        a bare ``np.empty((0,))`` loses the class axis and crashes
        ``predict_class``'s ``argmax(..., axis=-1)`` downstream. Falls back
        to the rank-1 empty only when the input carries no per-record spec
        (an empty Sample list) or the output spec cannot be traced."""
        arr = None
        if isinstance(data, np.ndarray):
            arr = data
        elif not isinstance(data, AbstractDataSet):
            try:
                arr = np.asarray(data)
            except (ValueError, TypeError):
                arr = None
        if arr is None or arr.ndim < 2 or arr.dtype == object:
            return np.empty((0,))
        try:
            if not self.model.is_built():
                self.model._ensure_built(
                    jnp.zeros((1,) + arr.shape[1:], jnp.asarray(arr[:0]).dtype)
                )
            spec = jax.eval_shape(
                lambda p, s, xx: self.model.apply(
                    p, s, xx, training=False, rng=None
                )[0],
                self.model.get_parameters(), self.model.get_state(),
                jnp.asarray(arr[:0]),
            )
        except Exception:  # output spec untraceable at batch 0 — degrade
            return np.empty((0,))
        return _tm(lambda s: np.empty(s.shape, s.dtype), spec)

    def predict_class(self, data) -> np.ndarray:
        """Argmax class indices, 1-based like the reference's Torch convention
        (``predictClass``, $DL/optim/Predictor.scala)."""
        out = self.predict(data)
        return np.argmax(out, axis=-1) + 1


class Evaluator:
    """model.evaluate(dataset, methods): one jitted step computes the model output
    plus every method's (numerator, count) counters; host folds results with ``+``
    (reference: $DL/optim/Evaluator.scala, DistriValidator, LocalValidator).

    Ragged-tail contract: the first batch fixes the step's static shape; a
    shorter final batch is PADDED back to it on host (``pad_minibatch``) and
    its padded output rows are sliced off before the metric fold — the same
    seam ``LocalOptimizer.validate()`` uses — so a sweep with a ragged tail
    compiles exactly ONE executable (it used to silently compile a second,
    replicated-layout one because the tail also skipped sharding)."""

    def __init__(self, model, batch_size: Optional[int] = None):
        self.model = model
        self.predictor = Predictor(model, batch_size)
        # method-name key -> (the exact method objects, jitted step). The
        # step CLOSES OVER the method objects, so a cache hit requires the
        # same instances — two same-named but differently-parameterized
        # methods (HitRatio(k=5) vs k=10) must never share a compiled step.
        self._steps: Dict[tuple, tuple] = {}

    def _step_for(self, methods: Sequence[ValidationMethod]):
        key = tuple(m.name for m in methods)
        cached = self._steps.get(key)
        if cached is not None and len(cached[0]) == len(methods) and all(
            a is b for a, b in zip(cached[0], methods)
        ):
            return cached[1]
        model = self.model

        def step(params, state, x, t):
            y, _ = model.apply(params, state, x, training=False, rng=None)
            return y, [m.metric(y, t) for m in methods]

        jitted = jax.jit(step)
        self._steps[key] = (tuple(methods), jitted)
        return jitted

    def evaluate(
        self, dataset, methods: Sequence[ValidationMethod]
    ) -> Dict[str, ValidationResult]:
        if not methods:
            raise ValueError(
                "evaluate(dataset) needs validation methods, e.g. [Top1Accuracy()]"
            )
        model = self.model
        methods = list(methods)

        # one jitted step serves every batch — the ragged tail is padded back
        # to the first batch's shape, so the whole sweep is ONE executable
        jitted = self._step_for(methods)
        totals: Dict[str, ValidationResult] = {}

        if not isinstance(dataset, AbstractDataSet):
            raise TypeError("Evaluator.evaluate expects an AbstractDataSet")

        n_dev = self.predictor._n_dev
        sharding = self.predictor._sharding
        expected: Optional[int] = None  # first batch fixes the static shape
        for batch in dataset.data(train=False):
            n = batch.size()
            if expected is None:
                expected = n
            target = batch.get_target()
            tail_n: Optional[int] = None
            if n < expected:
                padded = pad_minibatch(batch, expected)
                if padded is not None:
                    batch, tail_n = padded[0], n
            x = _tm(jnp.asarray, batch.get_input())
            t = _tm(jnp.asarray, batch.get_target())
            self.model._ensure_built(x)
            # shard by the PADDED size: the padded tail rides the same
            # sharded executable as the full batches instead of forcing a
            # second, replicated-layout compile
            if sharding is not None and batch.size() % n_dev == 0:
                x = _tm(lambda a: jax.device_put(a, sharding), x)
                t = _tm(lambda a: jax.device_put(a, sharding), t)
            y, pairs = jitted(model.get_parameters(), model.get_state(), x, t)
            if tail_n is not None:
                # pad rows poison the in-graph counters — slice them off the
                # OUTPUT and fold the tail's metrics eagerly on the real rows
                # (targets stay unpadded), exactly like validate()
                y_real = _tm(lambda a: a[:tail_n], y)
                for m in methods:
                    r = m(y_real, target)
                    totals[m.name] = (
                        totals[m.name] + r if m.name in totals else r
                    )
                continue
            for m, (num, cnt) in zip(methods, pairs):
                r = m.make_result(float(num), int(cnt))
                totals[m.name] = totals[m.name] + r if m.name in totals else r
        return totals


class PredictionService:
    """Thread-safe local serving (reference: $DL/optim/PredictionService.scala keeps
    a blocking queue of model clones). One XLA executable serves all threads; the
    lock only guards lazy build."""

    def __init__(self, model, pool_size: int = 1):
        # pool_size kept for API parity; XLA executables are reentrant so a single
        # compiled program replaces the reference's instance pool.
        self.pool_size = pool_size
        self._predictor = Predictor(model)
        self._lock = threading.Lock()

    def predict(self, x, single: bool = False) -> np.ndarray:
        """``single=True`` treats ``x`` as one record (adds/strips the batch dim)."""
        arr = np.asarray(x)
        batched = arr[None] if single else arr
        with self._lock:
            self._predictor.model._ensure_built(jnp.asarray(batched))
        out = self._predictor.predict(batched)
        return out[0] if single else out
