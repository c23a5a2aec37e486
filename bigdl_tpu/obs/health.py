"""Model-health observability: in-graph per-layer statistics + NaN attribution.

PR 3's telemetry answers "how fast is the run" and PR 4's resilience runtime
answers "recover when it breaks"; this module answers "*why* is the model
unhealthy". The reference framework leaned on driver-side visibility for this
(``TrainSummary`` per-parameter norms, SURVEY.md §5); here the equivalent is
computed **inside the compiled train step** so it costs no extra host syncs
and no recompiles (the PR 2 exactly-1-compile invariant holds with health
enabled — locked by ``tests/test_health.py``).

Design:

* :class:`HealthConfig` + :class:`HealthMonitor`, attached via
  ``Optimizer.set_health(...)`` (all three training paths). The step builders
  ask the monitor for a **pure jnp** statistics function; its output is a
  small fixed-shape f32 pytree (``{"layers": (L, 5)[, "acts": (A, 3)]}``)
  returned as one extra step output.
* Channels per parameter leaf (tree paths) or per flat-codec segment (the
  ZeRO-1 sharded path): Σg² (post-clip gradient), Σw² (updated weights),
  Σ(Δw)², non-finite count in grads, non-finite count in updated weights.
  Host-side these become grad/weight norms and the update/weight ratio.
* Activation statistics (mean/std/zero-fraction) ride the module forward-hook
  seam (``AbstractModule.register_forward_hook``): hooks stash a 3-vector
  under ``'_health_act'`` in the state pytree — the same jit-compatible
  channel ``'_aux_loss'`` uses — and the step extracts them in-graph. The
  zero-init entries are seeded at install time so the state STRUCTURE is
  identical on every call (no retrace).
* The host pulls the stats at the SAME one-step-late seam as the loss
  (:meth:`HealthMonitor.snapshot` is the single sanctioned device→host read —
  lint rule BDL008), emits a ``type="health"`` telemetry record every
  ``every_n_steps`` steps, and — when the divergence guard trips — attributes
  the failure to the **first non-finite layer path** and whether grads or
  weights poisoned it (:meth:`attribute_nonfinite`), carried on the
  ``DivergenceError`` into the ``rollback`` record.

Stats are computed in-graph on EVERY step once enabled (tiny fused
reductions; the stride bounds the host-side pull/record cost) so the
diverging step's counters are always available for attribution, whatever the
stride. With health disabled nothing changes: the step program, its
signature, and the driver loop are bit-identical to the pre-health build.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

__all__ = [
    "HealthConfig", "HealthMonitor", "ACT_STATE_KEY",
    "DriftConfig", "ActivationDrift",
]

# state-pytree key under which forward hooks stash activation statistics
ACT_STATE_KEY = "_health_act"


def pretty_path(path) -> str:
    """``(DictKey('Linear_0'), DictKey('weight'))`` -> ``Linear_0/weight``
    (shared with obs/profiler.py so health records and memory tables name
    layers identically)."""
    return "/".join(
        str(getattr(k, "key", getattr(k, "idx", k))) for k in path
    )


def flat_leaf_path(raw: str) -> str:
    """FlatParameter codec path (``['Linear_0']['weight']``) -> the same
    ``Linear_0/weight`` form as :func:`pretty_path` (shared with
    obs/profiler.py — the two views join on these names)."""
    return raw.replace("['", "").replace("']", "/").rstrip("/")

# per-layer stat channels, in matrix column order
STAT_CHANNELS = (
    "grad_sq", "weight_sq", "update_sq", "nonfinite_grads", "nonfinite_params"
)


@dataclass
class HealthConfig:
    """Knobs for :class:`HealthMonitor` (docs/observability.md).

    Args:
        every_n_steps: host-side sampling stride — a ``health`` record is
            emitted every N completed steps (device-side reductions run every
            step so divergence attribution never misses the poisoned step).
        per_layer: per-parameter-leaf statistics (the default). ``False``
            reduces to run-global scalars on device — cheaper for huge models
            (and on the ZeRO-1 path it avoids the per-element segment-id
            constant, which costs 4 bytes/param of HBM).
        activations: install forward hooks that record activation
            mean/std/zero-fraction per (leaf) module. Off by default — it
            rewrites module state structure (zero-init entries are seeded at
            install, so checkpoints written before/after enabling differ in
            state keys).
        activation_filter: ``f(path, module) -> bool`` selecting which leaf
            modules get a hook (default: all non-container modules).
        update_ratio_warn: auto-LR guard bound (None = off): when any
            per-layer update/weight ratio (or the global ratio with
            ``per_layer=False``) exceeds this bound for
            ``update_ratio_patience`` CONSECUTIVE emitted health samples, a
            ``warn`` telemetry record fires — the "your LR is about to blow
            this up" signal that lands BEFORE the divergence guard's NaN
            rollback. A healthy ratio sits around 1e-3; sustained >1e-1
            usually precedes divergence.
        update_ratio_patience: how many consecutive over-bound samples arm
            the warning (debounces a single clipped-spike step).
    """

    every_n_steps: int = 1
    per_layer: bool = True
    activations: bool = False
    activation_filter: Optional[Callable] = None
    update_ratio_warn: Optional[float] = None
    update_ratio_patience: int = 3

    def __post_init__(self):
        if self.every_n_steps < 1:
            raise ValueError(
                f"every_n_steps must be >= 1, got {self.every_n_steps}"
            )
        if self.update_ratio_patience < 1:
            raise ValueError(
                f"update_ratio_patience must be >= 1, got "
                f"{self.update_ratio_patience}"
            )


class HealthMonitor:
    """Builds the in-graph statistics functions and owns the host-side half:
    stride gating, the one-step-late pull, record formatting, and non-finite
    attribution. One monitor serves one optimizer; the layout bindings
    (parameter paths, flat-codec geometry, activation paths) are refreshed at
    every step construction, so retries and rebuilt models stay consistent."""

    def __init__(self, config: Optional[HealthConfig] = None):
        self.config = config or HealthConfig()
        self._paths: List[str] = []          # per-layer row labels
        self._act_paths: List[str] = []      # activation row labels
        self._seg_ids: Optional[np.ndarray] = None  # flat-codec segment ids
        self._mesh_axis: Optional[tuple] = None  # (axis_name, n_shards)
        self._hook_handles: list = []
        self._hooked_modules: list = []  # modules whose state we seeded
        self._hooked_model_id: Optional[int] = None
        self._ratio_breaches = 0  # consecutive over-bound health samples

    # ------------------------------------------------------- layout binding
    _pretty = staticmethod(pretty_path)

    def bind_tree(self, params) -> None:
        """Bind per-leaf paths from a parameter TREE (local/replicated/GSPMD
        paths); row order matches ``tree_stats``'s flatten order."""
        import jax

        pairs = jax.tree_util.tree_flatten_with_path(params)[0]
        self._paths = [self._pretty(p) for p, _ in pairs]
        self._seg_ids = None

    def bind_flat(self, fp) -> None:
        """Bind the flat-codec geometry (the ZeRO-1 sharded path): rows are
        the codec's leaves; a per-element segment-id vector maps flat offsets
        back to them for the in-shard segment reductions."""
        self._paths = [flat_leaf_path(p) for p in fp.paths]
        if self.config.per_layer:
            # the codec owns the segment-id machinery (shared with the fused
            # flat optimizer update's per-segment coefficient vectors)
            self._seg_ids = fp.segment_ids()
        else:
            self._seg_ids = None

    def bind_mesh_axis(self, axis_name: str, n_shards: int) -> None:
        """Bind the data-mesh-axis geometry for per-shard localization on
        the GSPMD/hybrid path: the step's ``shards`` stats rows map to
        ``<axis_name>[i]`` labels host-side."""
        self._mesh_axis = (str(axis_name), int(n_shards))

    def bind_acts(self, state) -> None:
        """Discover the ``'_health_act'`` entries the installed hooks seeded
        into the state pytree; row order matches the in-graph extraction
        (both use the same jax flatten order)."""
        import jax

        pairs = jax.tree_util.tree_flatten_with_path(state)[0]
        self._act_paths = [
            self._pretty(p[:-1])
            for p, _ in pairs
            if getattr(p[-1], "key", None) == ACT_STATE_KEY
        ]

    # ----------------------------------------------------- activation hooks
    def prepare(self, model) -> None:
        """Install activation hooks on ``model`` (idempotent per model):
        called by the optimizer after build, before the state pytree is read
        for the step — the seeded zero entries must be part of the traced
        input structure or call 2 would retrace."""
        if not self.config.activations:
            return
        if self._hooked_model_id == id(model):
            return
        self.remove_hooks()
        accept = self.config.activation_filter or (lambda path, m: True)
        for path, m in _walk_with_paths(model):
            if _is_container(m) or not accept(path, m):
                continue
            self._hook_handles.append(
                m.register_forward_hook(_activation_stat_hook)
            )
            _seed_act_state(m)
            self._hooked_modules.append(m)
        self._hooked_model_id = id(model)

    def remove_hooks(self) -> None:
        """Undo :meth:`prepare` completely: unhook every module AND drop the
        seeded/accumulated ``'_health_act'`` state entries, so a model after
        detach is bit-identical to one that never had health attached
        (``set_health(False)`` and monitor replacement both rely on this)."""
        for h in self._hook_handles:
            h.remove()
        for m in self._hooked_modules:
            m._state.pop(ACT_STATE_KEY, None)
        self._hook_handles = []
        self._hooked_modules = []
        self._hooked_model_id = None

    # ------------------------------------------------- device side (traced)
    def tree_stats(self, grads, old_params, new_params, new_state=None):
        """Pure-jnp per-leaf statistics over parameter TREES — called inside
        the jitted step (local, hybrid pjit, distri replicated). ``grads``
        is the post-clip effective gradient; ``new_params`` the updated
        weights. Returns ``{"layers": (L, 5)[, "acts": (A, 3)]}`` f32."""
        import jax
        import jax.numpy as jnp

        g_leaves = jax.tree_util.tree_leaves(grads)
        o_leaves = jax.tree_util.tree_leaves(old_params)
        n_leaves = jax.tree_util.tree_leaves(new_params)
        rows = []
        for g, o, n in zip(g_leaves, o_leaves, n_leaves):
            g = g.astype(jnp.float32)
            o = o.astype(jnp.float32)
            n = n.astype(jnp.float32)
            rows.append(jnp.stack([
                jnp.sum(g * g),
                jnp.sum(n * n),
                jnp.sum((n - o) ** 2),
                jnp.sum((~jnp.isfinite(g)).astype(jnp.float32)),
                jnp.sum((~jnp.isfinite(n)).astype(jnp.float32)),
            ]))
        mat = jnp.stack(rows)
        if not self.config.per_layer:
            mat = jnp.sum(mat, axis=0, keepdims=True)
        out = {"layers": mat}
        acts = self.act_stats(new_state)
        if acts is not None:
            out["acts"] = acts
        return out

    @staticmethod
    def _flat_cols(g, old, new):
        """The five stat channels (STAT_CHANNELS order) as per-element
        vectors over a flat slice — shared by the sharded and full-vector
        flat reductions."""
        import jax.numpy as jnp

        g = g.astype(jnp.float32)
        o = old.astype(jnp.float32)
        n = new.astype(jnp.float32)
        return (
            g * g,
            n * n,
            (n - o) ** 2,
            (~jnp.isfinite(g)).astype(jnp.float32),
            (~jnp.isfinite(n)).astype(jnp.float32),
        )

    def _flat_reduce(self, fp, cols, seg):
        """Segment-reduce the stat columns against the codec geometry (or
        collapse to one global row with ``per_layer=False``)."""
        import jax
        import jax.numpy as jnp

        if self.config.per_layer:
            nseg = len(fp.sizes)
            return jnp.stack(
                [jax.ops.segment_sum(c, seg, num_segments=nseg + 1)[:nseg]
                 for c in cols],
                axis=1,
            )
        return jnp.stack([jnp.sum(c) for c in cols])[None, :]

    def flat_shard_stats(self, fp, g_shard, old_shard, new_shard, me, axis):
        """Per-layer statistics from this device's SLICE of the flat ZeRO-1
        layout — segment reductions against the codec geometry, psum'd over
        ``axis`` so every device returns the identical replicated matrix.
        Called inside the shard_map'd sharded step."""
        import jax
        import jax.numpy as jnp

        cols = self._flat_cols(g_shard, old_shard, new_shard)
        seg = None
        if self.config.per_layer:
            seg = jax.lax.dynamic_slice(
                jnp.asarray(self._seg_ids), (me * fp.shard_size,),
                (fp.shard_size,),
            )
        return jax.lax.psum(self._flat_reduce(fp, cols, seg), axis)

    def mesh_shard_stats(self, x, t, n_shards: int):
        """Per-data-shard non-finite counts over the batch input/target
        trees — the GSPMD/hybrid path's mesh localization. Under pjit the
        global batch is sharded in contiguous row blocks along the data
        axis, so reshaping the leading dim to ``(n_shards, rows_per_shard)``
        and reducing per block compiles to shard-local reductions: when a
        poisoned record reaches the step, the resulting ``(n_shards, 2)``
        matrix names the exact mesh coordinate that carried it (the
        divergence rollback record's ``shard`` field)."""
        import jax
        import jax.numpy as jnp

        def per_shard_nonfinite(tree):
            tot = jnp.zeros((n_shards,), jnp.float32)
            for leaf in jax.tree_util.tree_leaves(tree):
                a = jnp.asarray(leaf)
                if a.ndim == 0 or a.shape[0] % n_shards:
                    continue  # not batch-led (sparse nnz columns etc.)
                nf = (~jnp.isfinite(a.astype(jnp.float32))).astype(
                    jnp.float32
                )
                tot = tot + jnp.sum(nf.reshape(n_shards, -1), axis=1)
            return tot

        return jnp.stack(
            [per_shard_nonfinite(x), per_shard_nonfinite(t)], axis=1
        )

    def act_stats(self, state):
        """Stack the hook-stashed activation rows out of the state pytree
        (in-graph); None when no hook entries exist. Discovers the entries
        from the TRACED state itself (not the host-side ``bind_acts`` row
        labels) so the in-graph extraction can never go stale against a
        state structure that changed after the step was cached."""
        if state is None:
            return None
        import jax
        import jax.numpy as jnp

        pairs = jax.tree_util.tree_flatten_with_path(state)[0]
        rows = [
            leaf for path, leaf in pairs
            if getattr(path[-1], "key", None) == ACT_STATE_KEY
        ]
        if not rows:
            return None
        return jnp.stack(rows).astype(jnp.float32)

    # ------------------------------------------------------------ host side
    def should_emit(self, iteration: int) -> bool:
        return iteration % self.config.every_n_steps == 0

    def snapshot(self, health) -> Dict[str, np.ndarray]:
        """THE one-step-late pull seam: materialize the step's health pytree
        on host. The arrays are ready by construction — the loss of the same
        step was already pulled — so this is a copy, not a new pipeline
        sync."""
        import jax

        return {
            k: np.asarray(jax.device_get(v))  # lint: disable=BDL008 the sanctioned one-step-late pull seam
            for k, v in health.items()
        }

    def record_fields(self, snap: Dict[str, np.ndarray]) -> Dict:
        """Format a pulled snapshot into the ``health`` record's fields
        (schema: docs/observability.md)."""
        mat = snap["layers"]
        g_sq = float(mat[:, 0].sum())
        w_sq = float(mat[:, 1].sum())
        u_sq = float(mat[:, 2].sum())
        fields: Dict = {
            "stride": self.config.every_n_steps,
            "global": {
                "grad_norm": math.sqrt(g_sq) if g_sq >= 0 else float("nan"),
                "weight_norm": math.sqrt(w_sq) if w_sq >= 0 else float("nan"),
                "update_ratio": _ratio(u_sq, w_sq),
                "nonfinite_grads": int(mat[:, 3].sum()),
                "nonfinite_params": int(mat[:, 4].sum()),
            },
        }
        if self.config.per_layer and len(self._paths) == mat.shape[0]:
            fields["layers"] = {
                path: {
                    "grad_norm": _sqrt(row[0]),
                    "weight_norm": _sqrt(row[1]),
                    "update_ratio": _ratio(float(row[2]), float(row[1])),
                    "nonfinite_grads": int(row[3]),
                    "nonfinite_params": int(row[4]),
                }
                for path, row in zip(self._paths, mat)
            }
        acts = snap.get("acts")
        if acts is not None and len(self._act_paths) == acts.shape[0]:
            fields["acts"] = {
                path: {
                    "mean": float(row[0]),
                    "std": float(row[1]),
                    "zero_frac": float(row[2]),
                }
                for path, row in zip(self._act_paths, acts)
            }
        quant = snap.get("quant")
        if quant is not None:
            # comms-quantizer telemetry (parallel/compression.py): rows are
            # [amax, saturated, underflow] per codec segment (+ padding
            # tail); the global block is what operators watch — sustained
            # underflow means the wire dtype is crushing this model's
            # gradients (error feedback re-injects it, but later)
            fields["quant"] = {
                "scale_amax": float(np.max(quant[:, 0])),
                "saturated": int(quant[:, 1].sum()),
                "underflow": int(quant[:, 2].sum()),
            }
            if (
                self.config.per_layer
                and len(self._paths) == quant.shape[0] - 1
            ):
                fields["quant"]["layers"] = {
                    path: {
                        "amax": float(row[0]),
                        "saturated": int(row[1]),
                        "underflow": int(row[2]),
                    }
                    for path, row in zip(self._paths, quant)
                }
        shards = snap.get("shards")
        if shards is not None and self._mesh_axis is not None:
            name, _n = self._mesh_axis
            fields["shards"] = {
                f"{name}[{i}]": {
                    "nonfinite_inputs": int(row[0]),
                    "nonfinite_targets": int(row[1]),
                }
                for i, row in enumerate(shards)
            }
        return fields

    def lr_guard_event(self, fields: Dict) -> Optional[Dict]:
        """The ``update_ratio`` auto-LR guard (docs/observability.md): feed
        each EMITTED health record's fields through this; returns the warn
        payload exactly once per breach streak — on the sample where the
        ratio has stayed above ``update_ratio_warn`` for
        ``update_ratio_patience`` consecutive samples — and None otherwise.
        A warning, not an action: it fires while the run is still finite,
        BEFORE the divergence guard's rollback machinery would."""
        bound = self.config.update_ratio_warn
        if bound is None:
            return None
        ratio = float(fields["global"]["update_ratio"])
        worst_layer = None
        layers = fields.get("layers")
        if layers:
            worst_layer, worst = max(
                layers.items(),
                key=lambda kv: _guard_key(kv[1]["update_ratio"]),
            )
            ratio = float(worst["update_ratio"])
        # NaN means the run already went non-finite — the divergence guard
        # owns that; the LR guard only watches the still-finite approach
        if math.isfinite(ratio) and ratio > bound:
            self._ratio_breaches += 1
        else:
            self._ratio_breaches = 0
            return None
        if self._ratio_breaches != self.config.update_ratio_patience:
            return None  # warn exactly once per streak, at the patience mark
        return {
            "reason": "update_ratio",
            "ratio": ratio,
            "bound": bound,
            "consecutive": self._ratio_breaches,
            "layer": worst_layer,
        }

    def attribute_nonfinite(
        self, snap: Dict[str, np.ndarray]
    ) -> Tuple[Optional[str], str]:
        """Name the FIRST layer (tree order) whose counters went non-finite
        and whether grads or weights poisoned it. ``(None, "loss")`` when
        every parameter counter is clean (e.g. a criterion-only NaN) or
        per-layer stats are off."""
        mat = snap["layers"]
        if self.config.per_layer and len(self._paths) == mat.shape[0]:
            for path, row in zip(self._paths, mat):
                if row[3] > 0:
                    return path, "grads"
                if row[4] > 0:
                    return path, "weights"
        else:
            if mat[:, 3].sum() > 0:
                return None, "grads"
            if mat[:, 4].sum() > 0:
                return None, "weights"
        return None, "loss"

    def attribute_shard(self, snap: Dict[str, np.ndarray]) -> Optional[str]:
        """GSPMD/hybrid mesh localization: name the FIRST data-axis shard
        whose input/target rows carried non-finite values on the diverged
        step (``"data[3]"``), or None when the step recorded no per-shard
        stats or every shard's rows were clean (the NaN was born in compute,
        which SPMD replicates — a per-axis blame would be fiction there)."""
        shards = snap.get("shards")
        if shards is None or self._mesh_axis is None:
            return None
        name, _n = self._mesh_axis
        for i, row in enumerate(shards):
            if row[0] > 0 or row[1] > 0:
                return f"{name}[{i}]"
        return None


# --------------------------------------------------------------------------
# serving-side activation drift
# --------------------------------------------------------------------------

@dataclass
class DriftConfig:
    """Knobs for :class:`ActivationDrift` (docs/serving.md).

    Args:
        ema_decay: weight of the history in the per-layer EMA baseline of
            each activation statistic (mean/std/zero-fraction).
        warn_z: |z-score| of the current mean or std against the baseline
            beyond which the layer is flagged (the serving batcher emits a
            ``warn`` record with ``reason: "activation_drift"``).
        min_samples: number of samples the baseline must absorb before
            breaches are reported (an empty baseline z-scores everything).
    """

    ema_decay: float = 0.9
    warn_z: float = 6.0
    min_samples: int = 3

    def __post_init__(self):
        if not 0.0 < self.ema_decay < 1.0:
            raise ValueError(f"ema_decay must be in (0,1), got {self.ema_decay}")
        if self.min_samples < 1:
            raise ValueError(
                f"min_samples must be >= 1, got {self.min_samples}"
            )


class ActivationDrift:
    """Serving-side activation-drift monitor (docs/serving.md).

    Rides the exact forward-hook seam :class:`HealthMonitor` uses for
    training-side activation statistics: pure-jnp hooks stash one
    (mean, std, zero_frac) f32 3-vector per module in the state pytree, so a
    serving ``Predictor(capture_state=True)`` carries them out of every
    compiled forward at zero extra host syncs. The batcher calls
    :meth:`sample` every N flushes — the ONE sampled device→host pull of the
    serving hot loop (a tiny fixed-shape matrix, the same sanctioned-seam
    contract as ``HealthMonitor.snapshot``). Each statistic keeps an EMA
    mean + EMA second moment; the current value's z-score against that
    baseline beyond ``warn_z`` flags the layer — the "your input
    distribution moved / your swapped model behaves differently" signal.
    """

    def __init__(self, config: Optional[DriftConfig] = None):
        self.config = config or DriftConfig()
        # per-model installs: {id(model): (model, handles, modules)}. A
        # hot-swap installs on the NEW model while the OLD version is still
        # serving, so two models can be hooked at once; the server releases
        # the old one only after the swap completes.
        self._installs: Dict[int, tuple] = {}
        self._ema_mean: Optional[np.ndarray] = None   # (A, 3)
        self._ema_sq: Optional[np.ndarray] = None     # (A, 3)
        self.samples = 0

    # ------------------------------------------------------------ install
    def install(self, model) -> None:
        """Install the activation hooks on ``model`` (idempotent per model).
        Does NOT touch any previously hooked model — during a hot-swap the
        old version keeps serving (and keeps its hook entries) until the
        server calls :meth:`release` on it after the swap. The EMA baseline
        is shared across versions, so drift across a swap is visible too."""
        if id(model) in self._installs:
            return
        handles, modules = [], []
        for _path, m in _walk_with_paths(model):
            if _is_container(m):
                continue
            handles.append(m.register_forward_hook(_activation_stat_hook))
            _seed_act_state(m)
            modules.append(m)
        self._installs[id(model)] = (model, handles, modules)

    def release(self, model) -> None:
        """Unhook ONE model + drop its seeded state entries (same detach
        contract as ``HealthMonitor.remove_hooks``) — called by the server
        on the retired version after a hot-swap."""
        entry = self._installs.pop(id(model), None)
        if entry is None:
            return
        _model, handles, modules = entry
        for h in handles:
            h.remove()
        for m in modules:
            m._state.pop(ACT_STATE_KEY, None)

    def remove(self) -> None:
        """Release every hooked model."""
        for _mid in list(self._installs):
            self.release(self._installs[_mid][0])

    # ------------------------------------------------------------- sample
    def sample(self, state) -> Optional[Dict]:
        """Pull the hook-stashed activation rows out of a captured state
        pytree, score them against the EMA baseline, fold them in, and
        return ``{"acts": {path: {mean, std, zero_frac, mean_z, std_z}},
        "breach": {"layer", "z"} | None, "samples": n}`` — or None when the
        state carries no hook entries."""
        if state is None:
            return None
        import jax

        paths: List[str] = []
        rows: List[np.ndarray] = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
            if getattr(path[-1], "key", None) == ACT_STATE_KEY:
                paths.append(pretty_path(path[:-1]))
                rows.append(np.asarray(jax.device_get(leaf)))  # lint: disable=BDL008 the sampled serving drift seam (every drift_every batches, never per request)
        if not rows:
            return None
        mat = np.stack(rows).astype(np.float64)
        d = self.config.ema_decay
        if self._ema_mean is None or self._ema_mean.shape != mat.shape:
            self._ema_mean = mat.copy()
            self._ema_sq = mat * mat
            self.samples = 1
            z = np.zeros_like(mat)
        else:
            var = np.maximum(self._ema_sq - self._ema_mean ** 2, 0.0)
            # RELATIVE noise floor on sigma: a steady workload collapses the
            # EMA variance to ~0, and an absolute epsilon would turn any
            # numerically tiny wobble into an astronomical z (spurious warn)
            sigma = np.maximum(np.sqrt(var),
                               1e-3 * np.abs(self._ema_mean) + 1e-6)
            z = (mat - self._ema_mean) / sigma
            self._ema_mean = d * self._ema_mean + (1.0 - d) * mat
            self._ema_sq = d * self._ema_sq + (1.0 - d) * mat * mat
            self.samples += 1
        acts = {
            p: {
                "mean": float(row[0]),
                "std": float(row[1]),
                "zero_frac": float(row[2]),
                "mean_z": round(float(zr[0]), 3),
                "std_z": round(float(zr[1]), 3),
            }
            for p, row, zr in zip(paths, mat, z)
        }
        breach = None
        if self.samples > self.config.min_samples:
            worst_i = int(np.argmax(np.max(np.abs(z[:, :2]), axis=1)))
            worst_z = float(np.max(np.abs(z[worst_i, :2])))
            if worst_z > self.config.warn_z and math.isfinite(worst_z):
                breach = {"layer": paths[worst_i], "z": round(worst_z, 3)}
        return {"acts": acts, "breach": breach, "samples": self.samples}


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _guard_key(v: float) -> float:
    """Sort key for the worst update ratio: NaN sorts LAST (a non-finite
    layer is the divergence guard's business, not the LR guard's)."""
    v = float(v)
    return v if math.isfinite(v) else float("-inf")


def _sqrt(v) -> float:
    v = float(v)
    return math.sqrt(v) if v >= 0 else float("nan")


def _ratio(u_sq: float, w_sq: float) -> float:
    """sqrt(update²/weight²) — the update/weight ratio (≈ lr·grad/weight for
    SGD; the classic "is my LR sane" dial). 0 for an all-zero weight."""
    if w_sq <= 0:
        return 0.0
    if u_sq < 0 or not math.isfinite(u_sq) or not math.isfinite(w_sq):
        return float("nan")
    return math.sqrt(u_sq / w_sq)


def _is_container(m) -> bool:
    from ..nn.module import Container

    return isinstance(m, Container)


def _walk_with_paths(model, prefix: str = ""):
    """Yield ``(path, module)`` over the module tree — hierarchical names
    (``Sequential_0/Linear_1``) where ``walk()`` yields bare modules."""
    path = f"{prefix}/{model.name()}" if prefix else model.name()
    yield path, model
    if _is_container(model):
        for child in model.modules:
            yield from _walk_with_paths(child, path)


def _activation_stat_hook(module, x, y):
    """Forward hook: mean / std / zero-fraction of the module output's first
    leaf, as one f32 3-vector stashed under ``'_health_act'``. Pure jnp —
    traced into the step like any other state update."""
    import jax
    import jax.numpy as jnp

    a = jax.tree_util.tree_leaves(y)[0].astype(jnp.float32)
    return {
        ACT_STATE_KEY: jnp.stack([
            jnp.mean(a),
            jnp.std(a),
            jnp.mean((a == 0).astype(jnp.float32)),
        ])
    }


def _seed_act_state(module) -> None:
    """Seed the zero-init state entry the hook will overwrite each forward —
    BEFORE the optimizer reads the state pytree, so input and output state
    structures agree and the step compiles exactly once."""
    import jax.numpy as jnp

    if ACT_STATE_KEY not in module._state:
        module._state[ACT_STATE_KEY] = jnp.zeros((3,), jnp.float32)
