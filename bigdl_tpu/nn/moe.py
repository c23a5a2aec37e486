"""Mixture-of-experts layer — the framework-surface wrapper over
``parallel.moe.moe_ffn`` (VERDICT r4 next #3).

Beyond-reference capability (the reference has no MoE; SURVEY.md §2.5
parallelism-inventory row records expert parallelism as beyond-reference):
a switch top-1 (or GShard top-2, ``router_top_k=2``) MoE FFN exposed as an ``AbstractModule`` so it drives
through the same Module/Optimizer UX as every other layer — serializable,
quantizable-sweep-visible, usable inside ``Sequential``/``Graph`` models,
trainable with ``LocalOptimizer``.

Two execution paths with IDENTICAL semantics (tested against each other and
against ``moe_ffn_reference``):

* dense (default): the dispatch → batched-expert → combine computation on
  one device, vectorized over experts (one-hot scatter into per-expert
  capacity buffers, the ``all_to_all`` replaced by a transpose). Used on a
  single device and under plain data parallelism.
* expert-parallel: ``parallel.moe.moe_ffn`` — experts one-per-device along
  an ``expert`` mesh axis, tokens carried by two ``lax.all_to_all`` hops.
  Engaged when ``expert_parallel=True`` and ``Engine``'s mesh carries the
  ``mesh_axis`` axis (e.g. ``Engine.init(mesh_axis_name='expert')``), or a
  mesh is injected with ``set_mesh``. Engage only at top level — not inside
  another ``shard_map`` (the DistriOptimizer dp wrapper); compose dp×ep
  with ``parallel.ExpertParallelOptimizer(data_axis=...)``, which binds
  ``batch_axis`` so tokens shard over both mesh axes.

Capacity semantics match the sharded layout in BOTH paths: tokens are
viewed as ``n_experts`` source shards, each with per-expert buffer
``ceil(T_local / E * capacity_factor * k)``; over-capacity entries bypass the
expert (zero output — compose the layer residually, the switch convention).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..utils import precision
from .initialization import Xavier
from .module import AbstractModule

_tm = jax.tree_util.tree_map

_ACTIVATIONS = {
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "tanh": jnp.tanh,
}


def _expert_ffn(p, h, activation):
    """One expert's FFN over (T, D) tokens; ``p`` holds unstacked leaves."""
    return _ACTIVATIONS[activation](h @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]


class MoE(AbstractModule):
    """MoE FFN, ``(..., D) -> (..., D)`` — switch top-1 (default) or
    GShard top-2 routing (``router_top_k=2``).

    Args:
        n_experts: expert count E (= the ``expert`` mesh-axis size when
            expert-parallel).
        ffn_size: per-expert hidden width F (default 4·D).
        capacity_factor: per-(source-shard, expert) buffer is
            ``ceil(T_local / E * capacity_factor * k)`` (``moe_capacity``;
            scales with ``router_top_k`` since each token consumes up to
            k slots).
        activation: 'relu' | 'gelu' | 'silu' | 'tanh'.
        router_top_k: 1 = switch routing (output scaled by the raw gate
            probability); 2 = GShard (each token combines its two best
            experts, weights normalized over the pair; second choices
            queue for capacity after ALL first choices).
        expert_parallel: opt into the ``moe_ffn`` sharded path when an
            ``expert`` mesh axis is available (see module docstring).
        mesh_axis: name of the expert mesh axis.
        batch_axis: optional data mesh axis for dp x ep composition —
            tokens shard over BOTH axes in the sharded path (set by
            ``ExpertParallelOptimizer(data_axis=...)``; the capacity
            accounting then runs per (data row, source device), see
            ``moe_ffn``).

    The token count (product of all leading dims) must be divisible by
    ``n_experts`` — the same requirement the sharded layout has.
    """

    def __init__(self, n_experts: int, ffn_size: Optional[int] = None,
                 capacity_factor: float = 1.25, activation: str = "relu",
                 expert_parallel: bool = False, mesh_axis: str = "expert",
                 aux_loss_coeff: float = 0.01, router_top_k: int = 1,
                 batch_axis: Optional[str] = None):
        super().__init__()
        if n_experts < 2:
            raise ValueError(f"n_experts must be >= 2, got {n_experts}")
        if activation not in _ACTIVATIONS:
            raise ValueError(
                f"activation must be one of {sorted(_ACTIVATIONS)}, "
                f"got {activation!r}")
        if not 1 <= router_top_k <= n_experts:
            raise ValueError(
                f"router_top_k {router_top_k} not in [1, {n_experts}]")
        # k=1: switch (raw-gate-prob output scaling); k=2: GShard
        # (normalized top-2 combine weights, choice-major capacity
        # priority, capacity scaled by k)
        self.router_top_k = router_top_k
        self.n_experts = n_experts
        self.ffn_size = ffn_size
        self.capacity_factor = capacity_factor
        self.activation = activation
        self.expert_parallel = expert_parallel
        self.mesh_axis = mesh_axis
        self.batch_axis = batch_axis
        # switch load-balancing loss (Fedus et al. 2021 eq. 4-6):
        # aux = E * sum_e f_e * P_e, f_e = dispatched fraction (argmax),
        # P_e = mean router prob. Without it a trained router collapses
        # onto few experts. Rides the state pytree as '_aux_loss'; the
        # optimizers fold model.auxiliary_loss_tree(new_state) into the
        # objective. 0 disables.
        self.aux_loss_coeff = aux_loss_coeff
        self.weight_init = Xavier()
        self._mesh = None  # runtime-injected; never serialized

    # ------------------------------------------------------------------ mesh
    def set_mesh(self, mesh) -> "MoE":
        """Inject the device mesh for the expert-parallel path (the mesh is
        runtime state, not topology — it is not serialized)."""
        self._mesh = mesh
        return self

    def _resolve_mesh(self):
        if self._mesh is not None:
            return self._mesh
        from ..utils.engine import Engine

        if Engine.is_initialized():
            mesh = Engine.mesh()
            if mesh is not None and self.mesh_axis in mesh.shape:
                if mesh.shape[self.mesh_axis] != self.n_experts:
                    raise ValueError(
                        f"{self.name()}: n_experts={self.n_experts} but the "
                        f"Engine mesh's {self.mesh_axis!r} axis has "
                        f"{mesh.shape[self.mesh_axis]} devices; size the "
                        "layer to the mesh or inject a matching mesh with "
                        "set_mesh()")
                return mesh
        return None

    # -------------------------------------------------------------- contract
    def infer_shape(self, in_spec):
        shape = tuple(in_spec.shape)
        if not shape:
            raise ValueError(f"{self.name()}: needs a trailing model dim, got a scalar")
        tokens = 1
        for s in shape[:-1]:
            tokens *= s
        if tokens % self.n_experts:
            raise ValueError(
                f"{self.name()}: token count {tokens} (product of leading dims "
                f"of {shape}) not divisible by n_experts={self.n_experts}"
            )
        return jax.ShapeDtypeStruct(shape, jnp.result_type(in_spec.dtype, jnp.float32))

    # ----------------------------------------------------------------- build
    def _build(self, rng, in_spec):
        d = in_spec.shape[-1]
        f = self.ffn_size or 4 * d
        e = self.n_experts
        ks = jax.random.split(rng, 3)
        params = {
            # small-init router (switch recipe): near-uniform initial routing
            "router_w": 0.02 * jax.random.normal(ks[0], (d, e)),
            "w1": self.weight_init(ks[1], (e, d, f), d, f),
            "b1": jnp.zeros((e, f)),
            "w2": self.weight_init(ks[2], (e, f, d), f, d),
            "b2": jnp.zeros((e, d)),
        }
        state = {"_aux_loss": jnp.zeros(())} if self.aux_loss_coeff else {}
        return params, state

    # ----------------------------------------------------------------- apply
    def _apply(self, params, state, x, training, rng):
        x = jnp.asarray(x)
        d = x.shape[-1]
        lead = x.shape[:-1]
        tokens = x.reshape(-1, d)
        b = tokens.shape[0]
        if b % self.n_experts:
            raise ValueError(
                f"{self.name()}: token count {b} not divisible by "
                f"n_experts {self.n_experts}")
        expert_params = {k: params[k] for k in ("w1", "b1", "w2", "b2")}
        mesh = self._resolve_mesh() if self.expert_parallel else None
        if mesh is not None:
            from ..parallel.moe import moe_ffn

            y = moe_ffn(
                params["router_w"], expert_params,
                lambda p, h: _expert_ffn(p, h, self.activation),
                tokens, mesh, axis=self.mesh_axis,
                capacity_factor=self.capacity_factor,
                router_top_k=self.router_top_k,
                batch_axis=self.batch_axis)
        else:
            y = self._dense(params["router_w"], expert_params, tokens)
        if self.aux_loss_coeff and training:
            # training only: eval forwards skip the extra GEMM and pass the
            # init-seeded '_aux_loss' state through unchanged (structure
            # stays stable). Router matmul redone outside any shard_map:
            # one (B, E) GEMM, negligible next to the expert FFNs, keeps
            # the aux term on the plain jit path for both execution modes
            probs = jax.nn.softmax(tokens @ params["router_w"], axis=-1)
            e = self.n_experts
            f_e = jnp.mean(
                jax.nn.one_hot(jnp.argmax(probs, axis=-1), e), axis=0)
            p_e = jnp.mean(probs, axis=0)
            aux = self.aux_loss_coeff * e * jnp.sum(
                jax.lax.stop_gradient(f_e) * p_e)
            state = {**state, "_aux_loss": aux}
        return y.reshape(*lead, d), state

    def _dense(self, router_w, expert_params, tokens):
        """Single-device dispatch/combine with the sharded layout's exact
        capacity semantics (``all_to_all`` becomes a transpose)."""
        from ..parallel.moe import _route, moe_capacity

        e, k = self.n_experts, self.router_top_k
        b, d = tokens.shape
        t_local = b // e
        capacity = moe_capacity(t_local, e, self.capacity_factor, k)
        xs = tokens.reshape(e, t_local, d)  # (S, T, D): S source shards
        logits = jnp.einsum("std,de->ste", xs, router_w)
        expert_id, slot, keep, w = jax.vmap(
            lambda lg: _route(lg, e, capacity, k))(logits)  # each (S, T, k)

        # dispatch: per-shard scatter into (E, C, D) send buffers; one
        # entry per kept (token, choice)
        def scatter(x_one, eid, sl, kp):
            buf = jnp.zeros((e, capacity, d), tokens.dtype)
            return buf.at[eid, sl].add(
                jnp.where(kp[..., None], x_one[:, None, :], 0.0))

        send = jax.vmap(scatter)(xs, expert_id, slot, keep)  # (S, E, C, D)
        recv = send.transpose(1, 0, 2, 3).reshape(e, e * capacity, d)
        out = jax.vmap(
            lambda p, h: _expert_ffn(p, h, self.activation)
        )(expert_params, recv)  # (E, S*C, D)
        back = out.reshape(e, e, capacity, d).transpose(1, 0, 2, 3)

        def gather(b_one, eid, sl, kp, ww):
            g = b_one[eid, jnp.clip(sl, 0, capacity - 1)]  # (T, k, D)
            return jnp.sum(
                jnp.where(kp[..., None], g, 0.0) * ww[..., None], axis=1)

        ys = jax.vmap(gather)(back, expert_id, slot, keep, w)
        return ys.reshape(b, d)


# --------------------------------------------------------------------------
# routed experts without capacity: the chip's share of an expert-parallel
# layer (top-k router, gated or relu2 experts, grouped matrix products)
# --------------------------------------------------------------------------

def _megablox():
    """JAX's grouped-matmul kernels for the TPU. The package rebinds the name
    ``gmm`` to its differentiable wrapper, so the module is fetched by path."""
    import importlib

    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _tile(size: int, most: int = 1024) -> int:
    """Largest multiple of 128 that divides ``size`` and is at most ``most``.
    Where none does (1856 = 14.5 x 128), the multiple of 128 whose tiles
    overhang ``size`` least, the largest of those: the grouped kernel masks a
    ragged last tile of the contracted axis and leaves a ragged last tile of
    the columns to the block's bounds, and ``size`` itself as the one tile
    does not fit the kernel's fast memory at 1856 (docs/performance.md has
    the probe's table). Nothing is padded in the parameter tree."""
    tiles = range(128, min(size, most) + 1, 128)
    fits = [t for t in tiles if size % t == 0]
    if fits or not tiles:
        return fits[-1] if fits else size
    return min(reversed(tiles), key=lambda t: -size % t)


def _tiling(k: int, n: int):
    """(rows, contracted, columns) tile of the TPU grouped kernel. Its own
    default of 128 each ran the expert products at 10 TFLOP/s on a v5e
    (12.7 ms for 32768 rows of 2304 -> 896, PR 28 chip probe); 512 rows by
    the widest divisors up to 1024 ran them in 1.24 ms, 109 TFLOP/s, and
    1024 rows did not fit the kernel's fast memory."""
    return 512, _tile(k), _tile(n)


def _grouped_impl(lhs, rhs, group_sizes, transpose_rhs=False):
    """Rows of ``lhs`` (M, K), sorted by group, times their group's matrix
    ``rhs`` (G, K, N) (or (G, N, K) with ``transpose_rhs``): float32 out.
    Rows past the groups' total are not computed: zero from
    ``jax.lax.ragged_dot``, UNWRITTEN memory from the TPU kernel, so callers
    select the rows they use (``_local_rows``) and never multiply the rest."""
    if jax.default_backend() == "tpu":
        n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
        return _megablox().gmm(lhs, rhs, group_sizes, jnp.float32,
                               _tiling(lhs.shape[1], n),
                               transpose_rhs=transpose_rhs)
    if transpose_rhs:
        rhs = rhs.swapaxes(1, 2)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)


# The transposed kernel keeps a (K, N) tile of float32 sums and its output
# block in fast memory: at 1024 x 1024 that is 17 MiB of the 16 the compiler
# allows a v5e kernel (a 3072-wide model's 1024-wide experts), at the 768 x
# 1024 of the widest that ran before it fits.
_TRANSPOSED_TILE_MOST = 768 * 1024


def _transposed_tiling(k: int, n: int):
    """``_tiling(k, n)``, the columns' tile cut to 768 where the (K, N) tile
    would pass ``_TRANSPOSED_TILE_MOST``."""
    rows, tk, tn = _tiling(k, n)
    if tk * tn > _TRANSPOSED_TILE_MOST:
        tn = _tile(n, 768)
    return rows, tk, tn


def _grouped_transposed(lhs, g, group_sizes, out_dtype):
    """Per group, ``lhs_rows^T (K, m_g) @ g_rows (m_g, N)`` -> (G, K, N)."""
    n_groups = group_sizes.shape[0]
    if jax.default_backend() == "tpu":
        return _megablox().tgmm(lhs.swapaxes(0, 1), g, group_sizes, out_dtype,
                                _transposed_tiling(lhs.shape[1], g.shape[1]),
                                num_actual_groups=n_groups)
    dims = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return jax.lax.ragged_dot_general(
        lhs, g, group_sizes, dims,
        preferred_element_type=jnp.float32).astype(out_dtype)


@jax.custom_vjp
def grouped_dot(lhs, rhs, group_sizes):
    """Grouped matrix product over the experts held: operands in the compute
    dtype, float32 accumulation and result, forward and backward (see
    ``precision.dot_acc32``). On the TPU the grouped kernel that ships with
    JAX (megablox) visits the tiles of the groups' rows only; elsewhere
    ``jax.lax.ragged_dot``."""
    dt = precision.compute_dtype()
    return _grouped_impl(lhs.astype(dt), rhs.astype(dt), group_sizes)


def _grouped_dot_fwd(lhs, rhs, group_sizes):
    return grouped_dot(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_dot_bwd(res, g):
    lhs, rhs, group_sizes = res
    dt = precision.compute_dtype()
    g = g.astype(dt)
    d_lhs = _grouped_impl(g, rhs.astype(dt), group_sizes, transpose_rhs=True)
    d_rhs = _grouped_transposed(lhs.astype(dt), g, group_sizes, jnp.float32)
    return d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype), None


grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


# Rows the sorted buffer gets for each row an even router would send to the
# experts held. Read on the chip before it was fixed (PERF.md section 6,
# PR 35); a constant like the kernel's tile, not an argument.
_SHARE_SLACK = 2


def buffer_rows(pairs: int, n_held: int, n_experts: int) -> int:
    """Rows of the buffer between the two permutations, from shapes alone:
    ``_SHARE_SLACK`` times the held experts' even share of the ``pairs``
    (token, choice) pairs, rounded up to the grouped kernel's row tile, and
    never more than a row for every pair (all experts held, or a share of
    half or more: then there is the one size and one pass)."""
    share = -(-_SHARE_SLACK * pairs * n_held // n_experts)
    return min(pairs, _round_up(share, _tiling(128, 128)[0]))


def _local_rows(rows, pos, local):
    """``rows[pos]`` where the sorted row is one of the ``local`` rows the
    grouped products computed, zero elsewhere: a select, never a product, so
    that what the kernel left unwritten there cannot reach a result."""
    return jnp.where((pos < local)[:, None], rows[pos], 0)


@jax.custom_vjp
def _rows_of_tokens(x, order, pos, local, k):
    """``x[order // k]``: the token of each sorted (token, choice) pair.
    ``pos`` is the inverse permutation of ``order``, so the gradient is a
    gather too (each token's k sorted rows, summed), not a scatter-add."""
    return x[order // k]


def _rows_fwd(x, order, pos, local, k):
    return x[order // k], (pos, local, k, x.shape[0])


def _rows_bwd(res, g):
    pos, local, k, t = res
    dx = jnp.sum(_local_rows(g, pos, local).reshape(t, -1, g.shape[-1]), axis=1)
    return dx, None, None, None, None


_rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


@jax.custom_vjp
def _pairs_of_rows(y, order, pos, local):
    """``y[pos]``: each (token, choice) pair's sorted row, back in pair
    order, zero for a pair of an absent expert; the gradient gathers with
    ``order``."""
    return _local_rows(y, pos, local)


def _pairs_fwd(y, order, pos, local):
    return _local_rows(y, pos, local), (order,)


def _pairs_bwd(res, g):
    (order,) = res
    return g[order], None, None, None


_pairs_of_rows.defvjp(_pairs_fwd, _pairs_bwd)


FORMS = ("gated", "relu2")


def _expert_weights(params):
    """The held experts' matrices in the order ``_held_experts`` takes them:
    (w_gate, w_up, w_down) of the gated form, (w_up, w_down) of ``relu2``."""
    return tuple(params[k] for k in ("w_gate", "w_up", "w_down") if k in params)


def _held_experts(xs, weights, sizes):
    """The held experts over sorted rows, each row by its group's matrices.
    Three matrices are the gated form, ``W_down(silu(W_gate x) * W_up x)``;
    two the ungated ``relu2``, ``W_down relu(W_up x)^2``, the square taken of
    the float32 product before the down product rounds it."""
    with jax.named_scope("moe_experts"):
        if len(weights) == 3:
            w_gate, w_up, w_down = weights
            h = jax.nn.silu(grouped_dot(xs, w_gate, sizes)) \
                * grouped_dot(xs, w_up, sizes)
        else:
            w_up, w_down = weights
            h = jnp.square(jax.nn.relu(grouped_dot(xs, w_up, sizes)))
        return grouped_dot(h, w_down, sizes)


# --- the sized buffer: blocks of C sorted rows, each token's rows ADDED into
# its place (C rows moved; gathering in pair order writes T*k whatever C is,
# and lost at every C below T*k on a v5e: PERF.md section 6, PR 35)

def _live_rows(rows, local):
    """``rows`` (C, D) of a block with those past its first ``local``
    zeroed: a select, never a product, as in ``_local_rows``."""
    live = jnp.arange(rows.shape[0], dtype=jnp.int32) < local
    return jnp.where(live[:, None], rows, 0)


@jax.custom_vjp
def _block_of_tokens(x, tokens, local):
    """``x[tokens]``: the token of each sorted pair of a block. The gradient
    adds each of the block's ``local`` live rows into its token's place,
    summed in float32."""
    return x.at[tokens].get(mode="promise_in_bounds")


def _block_fwd(x, tokens, local):
    return _block_of_tokens(x, tokens, local), (tokens, local, x.shape[0])


def _block_bwd(res, g):
    tokens, local, t = res
    dx = jnp.zeros((t, g.shape[1]), jnp.float32).at[tokens].add(
        _live_rows(g, local).astype(jnp.float32), mode="promise_in_bounds")
    return dx.astype(g.dtype), None, None


_block_of_tokens.defvjp(_block_fwd, _block_bwd)


@jax.custom_vjp
def _block_of_weights(top_p, first, pos, local):
    """The router's weight of each sorted pair ``first`` (C,) of a block;
    the gradient goes back in pair order by a gather with ``pos``, the
    pair's place in the sort less the block's start."""
    return top_p.reshape(-1).at[first].get(mode="promise_in_bounds")


def _weights_fwd(top_p, first, pos, local):
    return _block_of_weights(top_p, first, pos, local), (pos, local, top_p.shape)


def _weights_bwd(res, g):
    pos, local, shape = res
    mine = (pos >= 0) & (pos < local)
    return jnp.where(mine, g.at[pos].get(mode="clip"), 0).reshape(shape), \
        None, None, None


_block_of_weights.defvjp(_weights_fwd, _weights_bwd)


def _block_pass(acc, x, weights, top_p, first, pos, sizes, local):
    """``acc`` (T, D) float32 plus the held experts' part of the layer from
    one block of the sort: its pairs ``first`` (C,), their groups' ``sizes``
    inside the block, its first ``local`` rows those of held experts; x
    (T, D) in the compute dtype, ``weights`` as ``_held_experts`` takes them.
    The products and what lies between them run over C rows."""
    with jax.named_scope("moe_route"):
        tokens = first // top_p.shape[1]
        xs = _block_of_tokens(x, tokens, local)
    ys = _held_experts(xs, weights, sizes)                      # (C, D)
    with jax.named_scope("moe_route"):
        # the select before the product: rows past ``local`` are unwritten
        weighted = _live_rows(ys, local) \
            * _block_of_weights(top_p, first, pos, local)[:, None]
        return acc.at[tokens].add(weighted, mode="promise_in_bounds")


def _blocks(c: int, order, pos, group_sizes, local):
    """``block(i)``: (first, pos, sizes, local) of the i-th run of ``c``
    sorted rows, as ``_block_pass`` takes them."""
    pairs = order.shape[0]
    padded = jnp.pad(order, (0, _round_up(pairs, c) - pairs))
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes

    def block(i):
        at = i * c
        sizes = jnp.clip(ends - at, 0, c) - jnp.clip(starts - at, 0, c)
        return (jax.lax.dynamic_slice(padded, (at,), (c,)), pos - at,
                sizes.astype(jnp.int32), jnp.clip(local - at, 0, c))

    return block


def _while_blocks(c: int, local, turn, start):
    """``turn(i, carried)`` for every block of ``c`` rows that holds one of
    the ``local`` rows of held experts."""
    return jax.lax.while_loop(
        lambda carry: carry[0] * c < local,
        lambda carry: (carry[0] + 1, turn(*carry)),
        (jnp.int32(0), start))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _block_by_block(c: int, x, weights, top_p, order, pos, group_sizes, local):
    """``_block_pass`` over the sorted rows in blocks of ``c``, as many as
    hold the ``local`` pairs of held experts: one where they fit the buffer
    (the sizing's case), more in a step whose routing overflows it, so that
    no pair is dropped and one set of kernels at one shape serves both. The
    trip count is the step's own, so the loop has its own differentiation
    rule: the residuals are the inputs, each block's own stay inside its
    turn, and the gradients are the blocks' sums. (A ``cond`` between this
    size and the full one compiled both sets of kernels, and differentiated
    as it stands would have written the full-size residuals every step.)"""
    block = _blocks(c, order, pos, group_sizes, local)
    floats = (x, weights, top_p)
    return _while_blocks(
        c, local, lambda i, acc: _block_pass(acc, *floats, *block(i)),
        jnp.zeros((x.shape[0], weights[-1].shape[2]), jnp.float32))


def _block_by_block_fwd(c, *args):
    return _block_by_block(c, *args), args


def _block_by_block_bwd(c, args, g):
    *floats, order, pos, group_sizes, local = args
    block = _blocks(c, order, pos, group_sizes, local)

    def add_grads(i, total):
        _, pull = jax.vjp(
            lambda *floats: _block_pass(jnp.zeros_like(g), *floats, *block(i)),
            *floats)
        return _tm(jnp.add, total, pull(g))

    total = _while_blocks(c, local, add_grads,
                          _tm(jnp.zeros_like, tuple(floats)))
    return (*total, None, None, None, None)


_block_by_block.defvjp(_block_by_block_fwd, _block_by_block_bwd)


def route_top_k(x, router_w, top_k: int, scaling: float = 1.0):
    """Softmax router in float32 over ALL experts: x (T, D) -> the k largest
    probabilities (T, k), renormalised over the chosen and multiplied by
    ``scaling``, and their expert ids (T, k)."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    w = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return (w if scaling == 1.0 else w * scaling), top_e


def route_sigmoid_top_k(x, router_w, bias, top_k: int, scaling: float = 1.0):
    """Sigmoid router in float32 over ALL experts (DeepSeek-V3's, ``noaux_tc``
    without groups): ``s = sigmoid(x W_r)``; the k experts with the largest
    ``s + bias`` are chosen (``bias`` (E,) or None: it enters the choice and
    nothing else, so it takes no gradient); their weights are ``s`` over
    the chosen, divided by their sum + 1e-20, times ``scaling``. -> (weights
    (T, k), expert ids (T, k))."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, top_e = jax.lax.top_k(
        s if bias is None else s + jax.lax.stop_gradient(bias), top_k)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    w = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    return (w if scaling == 1.0 else w * scaling), top_e


def selection_bias_update(bias, top_e, rate: float):
    """The router's selection bias after one step (DeepSeek-V3,
    arXiv:2412.19437 section 2.1.2): ``b_e + rate * sign(mean_e' c_e' - c_e)``
    with ``c_e`` the (token, choice) pairs of this step that chose expert
    ``e``, over all experts of the router."""
    counts = jnp.bincount(top_e.reshape(-1), length=bias.shape[0]).astype(
        jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


def routed_experts(x, params, *, n_experts: int, experts_held, top_k: int,
                   route=None):
    """x (T, D) -> (this share's part of the layer's result (T, D), counters,
    the chosen expert ids (T, k)).

    Every (token, choice) pair is kept: the pairs are sorted by the slot of
    their expert among the experts held (pairs of absent experts last), the
    grouped products (three of the gated form, two of ``relu2``: what
    ``params`` holds) run over the held experts' rows, and each token
    sums its k rows weighted by the router. No capacity, no drop. The rows
    between the two permutations live in a buffer of ``buffer_rows(T*k,
    n_held, n_experts)`` rows. Where all experts (or half of them and more)
    are held that is a row for every pair and the layer is one pass, each
    pair gathering its row. Where fewer are, it is twice the held experts'
    even share, so that the gathers, the gate and the sums cross the local
    pairs' rows and not the absent experts'; a step whose local pairs
    overflow it goes over the sort block by block (``_block_by_block``;
    counter ``moe_overflow_layers``). ``route(x, router_w, top_k) ->
    (weights, ids)`` is the router (``route_top_k`` where none is given)."""
    held = jnp.asarray(experts_held, jnp.int32)
    n_held = held.shape[0]
    pairs = x.shape[0] * top_k
    c = buffer_rows(pairs, n_held, n_experts)
    with jax.named_scope("moe_route"):
        top_p, top_e = (route or route_top_k)(x, params["router"], top_k)
        # slot among the held experts, n_held for an absent one
        slot_of = jnp.full((n_experts,), n_held, jnp.int32).at[held].set(
            jnp.arange(n_held, dtype=jnp.int32))
        slot = slot_of[top_e].reshape(-1)                       # (T*k,)
        order = jnp.argsort(slot, stable=True).astype(jnp.int32)
        pos = jnp.zeros_like(order).at[order].set(
            jnp.arange(pairs, dtype=jnp.int32))
        group_sizes = jnp.bincount(slot, length=n_held + 1)[:n_held].astype(
            jnp.int32)
        local = jnp.sum(group_sizes)  # pairs that hit an expert held here
    xc = x.astype(precision.compute_dtype())
    weights = _expert_weights(params)
    if c == pairs:  # a row for every pair: one pass, pairs gather their rows
        with jax.named_scope("moe_route"):
            xs = _rows_of_tokens(xc, order, pos, local, top_k)
        ys = _held_experts(xs, weights, group_sizes)            # (T*k, D)
        with jax.named_scope("moe_route"):
            rows = _pairs_of_rows(ys, order, pos, local).reshape(
                x.shape[0], top_k, -1)
            out = jnp.sum(rows * top_p[..., None], axis=1)
    else:
        out = _block_by_block(c, xc, weights, top_p, order, pos, group_sizes,
                              local)
    with jax.named_scope("moe_route"):
        blocks = -(-local // c)   # turns of the loop (one, where c == pairs)
        counters = {
            "moe_pairs_local": local.astype(jnp.float32),
            "moe_load_max_over_mean": jnp.max(group_sizes) / jnp.maximum(
                jnp.mean(group_sizes.astype(jnp.float32)), 1.0),
            # pairs of held experts that the blocks taken had no row for
            "moe_dropped_pairs": jnp.maximum(
                local - blocks * c, 0).astype(jnp.float32),
            # 1 from a layer whose local pairs overflowed the sized buffer
            "moe_overflow_layers": (blocks > 1).astype(jnp.float32),
        }
    return out.astype(x.dtype), counters, top_e


SCORINGS = ("softmax", "sigmoid")


class RoutedExperts(AbstractModule):
    """Top-k routed experts without capacity: ``(..., D) -> (..., D)``.

    ``p = softmax(x W_r)`` over ``n_experts`` in float32; the ``top_k``
    largest, renormalised over the chosen; ``sum_e w_e W_down,e(silu(
    W_gate,e x) * W_up,e x)`` over the chosen experts THIS MODULE HOLDS
    (``experts_held``: ids among ``range(n_experts)``, default all). That is
    ``form="gated"``, three matrices an expert; ``form="relu2"`` is the
    ungated ``W_down,e relu(W_up,e x)^2``, two matrices an expert (no
    ``w_gate`` leaf, and the shared expert of the same form: ``shared_in``
    (D, shared_size)), through the same paths. Held
    fewer than all, it is one chip's share of an expert-parallel layer run
    without its exchange: the router keeps its width, pairs routed to absent
    experts add nothing, and the shares of all chips sum to the whole layer
    (``tests/test_decoder_lm.py``, the share test). A share of less than
    half keeps its sorted rows in a buffer sized from the share, not from
    the pairs (``buffer_rows``: twice the held experts' even share), and a
    step whose local pairs overflow it goes over the sort block by block:
    nothing is dropped, ``moe_overflow_layers`` counts the layers that did
    (``routed_experts``). Beside ``MoE`` (switch / GShard with capacity
    buffers that drop) until ROADMAP D2 merges them.

    ``routed_scaling`` multiplies the chosen weights of either router.
    ``scoring="sigmoid"`` is DeepSeek-V3's router (``route_sigmoid_top_k``):
    sigmoid scores, weights normalised over the chosen. With ``bias_update_rate`` (its auxiliary-loss-free
    balancing) the choice is by ``s + b``: ``b`` (E,) is STATE, not a
    parameter (``selection_bias``: zero at the start, no gradient, no
    optimizer slot), and a training forward hands on ``b + rate * sign(mean
    count - count)`` from this step's counts over all ``n_experts``, as batch
    norm hands on its running statistics. ``shared_size`` adds one MLP of
    the experts' form and that width that every token passes, whole on every chip, to the routed
    sum (scope ``moe_shared``); in the share test it counts once. With
    ``shared_gate`` its output is multiplied by ``sigmoid(x w_s)``, one
    scalar a token (``shared_gate`` (D, 1), float32), as Qwen2-MoE's shared
    expert is.

    State: ``{"_counters": {moe_pairs_local, moe_load_max_over_mean,
    moe_dropped_pairs, moe_overflow_layers[, moe_bias_abs_max]}[,
    "selection_bias"]}``, see ``AbstractModule.counters_tree``."""

    def __init__(self, n_experts: int, ffn_size: int, top_k: int,
                 experts_held=None, init_std: float = 0.02,
                 scoring: str = "softmax", routed_scaling: float = 1.0,
                 bias_update_rate: Optional[float] = None,
                 shared_size: int = 0, form: str = "gated",
                 shared_gate: bool = False):
        super().__init__()
        if form not in FORMS:
            raise ValueError(f"form {form!r}: one of {FORMS}")
        held = tuple(range(n_experts) if experts_held is None else experts_held)
        if not held or not all(0 <= e < n_experts for e in held) \
                or len(set(held)) != len(held):
            raise ValueError(f"experts_held {held} are not distinct ids "
                             f"among {n_experts} experts")
        if not 1 <= top_k <= n_experts:
            raise ValueError(f"top_k {top_k} not in [1, {n_experts}]")
        if scoring not in SCORINGS:
            raise ValueError(f"scoring {scoring!r}: one of {SCORINGS}")
        if scoring == "softmax" and bias_update_rate is not None:
            raise ValueError("a selection bias belongs to scoring='sigmoid'")
        if shared_gate and not shared_size:
            raise ValueError("shared_gate gates a shared expert: give "
                             "shared_size")
        self.n_experts, self.ffn_size, self.top_k = n_experts, ffn_size, top_k
        self.experts_held = held
        self.init_std = init_std
        self.scoring, self.routed_scaling = scoring, float(routed_scaling)
        self.bias_update_rate = bias_update_rate
        self.shared_size, self.form = shared_size, form
        self.shared_gate = shared_gate

    def infer_shape(self, in_spec):
        return jax.ShapeDtypeStruct(tuple(in_spec.shape), in_spec.dtype)

    def _build(self, rng, in_spec):
        d, f, e = in_spec.shape[-1], self.ffn_size, len(self.experts_held)
        ks = jax.random.split(rng, 4)
        normal = lambda k, shape: self.init_std * jax.random.normal(  # noqa: E731
            k, shape, jnp.float32)
        gated = self.form == "gated"
        params = {"router": normal(ks[0], (d, self.n_experts))}
        if gated:
            params["w_gate"] = normal(ks[1], (e, d, f))
        params.update(w_up=normal(ks[2], (e, d, f)),
                      w_down=normal(ks[3], (e, f, d)))
        zero = jnp.zeros((), jnp.float32)
        state = {"_counters": {
            "moe_pairs_local": zero, "moe_load_max_over_mean": zero,
            "moe_dropped_pairs": zero, "moe_overflow_layers": zero}}
        if self.shared_size:
            k_in, k_out = jax.random.split(jax.random.fold_in(rng, 4))
            params.update(
                shared_in=normal(k_in, (d, (1 + gated) * self.shared_size)),
                shared_out=normal(k_out, (self.shared_size, d)))
            if self.shared_gate:
                params["shared_gate"] = normal(
                    jax.random.fold_in(rng, 5), (d, 1))
        if self.bias_update_rate is not None:
            state["selection_bias"] = jnp.zeros((self.n_experts,), jnp.float32)
            state["_counters"]["moe_bias_abs_max"] = zero
        return params, state

    def _apply(self, params, state, x, training, rng):
        x = jnp.asarray(x)
        tokens = x.reshape(-1, x.shape[-1])
        bias = state.get("selection_bias")
        route = functools.partial(route_top_k, scaling=self.routed_scaling)
        if self.scoring == "sigmoid":
            route = lambda x, w, k: route_sigmoid_top_k(  # noqa: E731
                x, w, bias, k, self.routed_scaling)
        out, counters, top_e = routed_experts(
            tokens, params, n_experts=self.n_experts,
            experts_held=self.experts_held, top_k=self.top_k, route=route)
        new_state = {"_counters": counters}
        if bias is not None:
            with jax.named_scope("moe_route"):
                if training:
                    bias = selection_bias_update(bias, top_e,
                                                 self.bias_update_rate)
                new_state["selection_bias"] = bias
                counters["moe_bias_abs_max"] = jnp.max(jnp.abs(bias))
        if self.shared_size:
            with jax.named_scope("moe_shared"):
                h = precision.dot_acc32(tokens, params["shared_in"])
                if self.form == "gated":
                    a, b = jnp.split(h, 2, axis=-1)
                    h = jax.nn.silu(a) * b
                else:
                    h = jnp.square(jax.nn.relu(h))
                shared = precision.dot_acc32(h, params["shared_out"])
                if self.shared_gate:
                    shared = shared * jax.nn.sigmoid(precision.dot_acc32(
                        tokens, params["shared_gate"]))
                out = out + shared.astype(x.dtype)
        return out.reshape(x.shape), new_state
