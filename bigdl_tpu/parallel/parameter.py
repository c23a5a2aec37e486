"""Flat-parameter plumbing — the TPU counterpart of ``AllReduceParameter``.

Reference behavior (SURVEY.md §2.5): ``$DL/parameters/AllReduceParameter.scala``
compacts all layer weights into ONE flat vector, splits it into partitionNum
slices, and per iteration does getWeights (all-gather) → putGradients +
aggregateGradientPartition (reduce-scatter) → sharded optimizer update on the
owned slice → sendWeightPartition (publish). Net effect: reduce-scatter +
all-gather with ZeRO-1-style sharded optimizer state, fp16 on the wire.

TPU-native design: the same decomposition as XLA collectives inside one jitted
step — ``lax.psum_scatter`` for gradient slices, ``lax.all_gather`` for updated
weights, both riding ICI. This class owns the tree↔flat-vector mapping (static
shapes, computed once) and the per-device slice geometry. The fp16 wire format
becomes an optional bf16 cast before the scatter (native TPU dtype).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class FlatParameter:
    """Static tree↔vector codec, padded so the vector splits evenly across shards."""

    def __init__(self, params_tree: Any, n_shards: int):
        pairs, self.treedef = jax.tree_util.tree_flatten_with_path(params_tree)
        self.paths = [jax.tree_util.keystr(p) for p, _ in pairs]
        leaves = [l for _, l in pairs]
        self.shapes = [l.shape for l in leaves]
        self.dtypes = [l.dtype for l in leaves]
        self.sizes = [int(np.prod(s)) for s in self.shapes]
        self.total = int(sum(self.sizes))
        self.n_shards = n_shards
        self.padded_total = ((self.total + n_shards - 1) // n_shards) * n_shards
        self.shard_size = self.padded_total // n_shards
        self._offsets = np.cumsum([0] + self.sizes[:-1]).tolist()
        self._segment_ids: Optional[np.ndarray] = None

    def matches(self, params_tree: Any) -> bool:
        """True when ``params_tree`` has the exact structure/shapes/dtypes this
        codec was built from — the guard step caches use to reuse a codec (and
        its compiled flatten/unflatten) across retry attempts."""
        try:
            pairs, treedef = jax.tree_util.tree_flatten_with_path(params_tree)
        except (TypeError, ValueError):
            return False
        return (
            treedef == self.treedef
            and [l.shape for _, l in pairs] == self.shapes
            and [l.dtype for _, l in pairs] == self.dtypes
        )

    def segment_ids(self) -> np.ndarray:
        """Per-element int32 leaf-index vector over the padded flat layout
        (padding tail = ``len(sizes)``, one past the last real segment) — THE
        segment-id machinery shared by the health segment reductions
        (``obs/health.py``) and the per-segment hyperparameter coefficients
        of the fused flat optimizer update. Built once, cached."""
        if self._segment_ids is None:
            seg = np.repeat(
                np.arange(len(self.sizes), dtype=np.int32), self.sizes
            )
            pad = self.padded_total - self.total
            if pad:
                seg = np.concatenate(
                    [seg, np.full((pad,), len(self.sizes), np.int32)]
                )
            self._segment_ids = seg
        return self._segment_ids

    def coefficient_vector(self, leaf_fn: Callable[[str], float]) -> np.ndarray:
        """Per-element f32 coefficient vector from a per-leaf scalar:
        ``leaf_fn(path) -> float`` evaluated once per codec leaf and repeated
        over its elements (padding tail = 0). This is how per-segment
        hyperparameters (weight-decay exclusions, per-layer LR scales) are
        precomputed ONCE as a constant for the fused segment-wise
        ``OptimMethod.update_flat`` — no per-leaf kernels in the hot loop."""
        per_leaf = np.asarray(
            [float(leaf_fn(p)) for p in self.paths], np.float32
        )
        seg = self.segment_ids()
        # index one past the end maps the padding tail to coefficient 0
        return np.concatenate([per_leaf, np.zeros((1,), np.float32)])[seg]

    def zero_pad(self, vec: jnp.ndarray) -> jnp.ndarray:
        """Re-zero the padding tail of a full padded vector. The tail's
        (g=0, p=0, slots=0) inputs are inert for most update rules, but not
        all: Adamax's ``|g|+eps`` guard (eps=1e-38) is subnormal and flushes
        to zero on CPU/TPU, so its tail divides 0/0 → NaN. With the vector
        now the CARRIED (donated) master state, a poisoned tail would
        persist forever — the step builders re-zero it after every fused
        update. No-op when the layout has no padding (``n_shards=1``)."""
        if self.padded_total == self.total:
            return vec
        return vec.at[self.total:].set(0.0)

    def zero_pad_shard(self, shard: jnp.ndarray, index) -> jnp.ndarray:
        """Per-shard twin of :meth:`zero_pad` for the ZeRO-1 sharded update,
        where only the LAST shard holds padding and the shard index is a
        traced ``axis_index``. An iota+select pass that fuses into the
        update chain — no constant table, no concatenate."""
        if self.padded_total == self.total:
            return shard
        gidx = index * self.shard_size + jnp.arange(
            self.shard_size, dtype=jnp.int32
        )
        return jnp.where(gidx < self.total, shard, 0.0)

    def shard_bounds(self, i: int) -> Tuple[int, int]:
        """[start, stop) of shard ``i`` within the padded flat vector."""
        if not 0 <= i < self.n_shards:
            raise IndexError(f"shard {i} out of range [0, {self.n_shards})")
        return i * self.shard_size, (i + 1) * self.shard_size

    def path_of_offset(self, offset: int) -> str:
        """Parameter path owning flat ``offset`` ('<padding>' for the tail) —
        turns a flat-vector finding back into a module-parameter name."""
        if not 0 <= offset < self.padded_total:
            raise IndexError(f"offset {offset} out of range [0, {self.padded_total})")
        if offset >= self.total:
            return "<padding>"
        j = int(np.searchsorted(np.asarray(self._offsets), offset, side="right")) - 1
        return self.paths[j]

    def flatten(self, tree) -> jnp.ndarray:
        """Tree → padded 1-D f32 vector (pure; jit-friendly)."""
        leaves = self.treedef.flatten_up_to(tree)
        vec = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
        pad = self.padded_total - self.total
        if pad:
            vec = jnp.concatenate([vec, jnp.zeros((pad,), jnp.float32)])
        return vec

    def unflatten(self, vec: jnp.ndarray):
        """Padded vector → tree with original shapes/dtypes (pure; jit-friendly).

        Inside jit this is the tree VIEW of the flat master state: ONE
        ``lax.split`` of the vector into the leaves and the padding tail
        (dropped), each piece reshaped and cast. The forward/backward
        consume these views while the padded flat vector stays the carried
        (donated) training state. The split's transpose is one
        ``concatenate`` of the leaves' cotangents and the tail's zeros, which
        XLA writes in place: each leaf's gradient lands in its slot of the
        flat gradient. Per-leaf ``dynamic_slice`` views would transpose to
        one zero-padded full-length vector per leaf, all summed (18.8 ms of
        ResNet-50's 143 ms ZeRO-1 step on a TPU v5e)."""
        pad = self.padded_total - self.total
        pieces = jax.lax.split(vec, self.sizes + [pad])
        leaves = [
            piece.reshape(shape).astype(dtype)
            for piece, shape, dtype in zip(pieces, self.shapes, self.dtypes)
        ]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # ------------------------------------------------- slot-vector tree views
    def slots_tree_view(self, slots: Dict[str, jnp.ndarray]) -> Dict[str, Any]:
        """Flat slot vectors (``{"velocity": (padded_total,)}``) → per-leaf
        trees mirroring the parameter tree. Checkpoints persist THIS view so
        flat- and tree-representation runs write bit-compatible manifests
        (``utils/serialization.py`` slot layout contract)."""
        return {
            k: self.unflatten(v)
            if getattr(v, "shape", None) == (self.padded_total,)
            else v  # scalar slot state (custom methods) passes through
            for k, v in slots.items()
        }

    def slots_from_tree(self, tree_slots: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
        """Inverse of :meth:`slots_tree_view`: per-leaf slot trees → flat f32
        vectors (padding tail re-zeroed). Resume re-flattens exactly once."""
        return {
            k: self.flatten(v)
            if isinstance(v, (dict, list, tuple)) or np.ndim(v) > 0
            else v  # scalar slot state (custom methods) passes through
            for k, v in tree_slots.items()
        }
