"""Expert parallelism — switch/GShard MoE with ``all_to_all`` dispatch.

Beyond-reference capability (with ``pipeline.py`` this completes the
dp/tp/pp/sp/ep axis set): E experts live one-per-device along an
``expert`` mesh axis; tokens are batch-sharded on the same axis, a top-1
router assigns each token an expert, and two ``lax.all_to_all`` hops carry
tokens to their expert's device and back — the Switch-Transformer layout
(Fedus et al. 2021, PAPERS.md) expressed as one shard_map program over XLA
collectives on the ICI.

Static shapes throughout (the TPU requirement): each device reserves a
fixed per-(source, expert) capacity ``C``; tokens beyond capacity are
DROPPED from the expert path and pass through as zeros (the standard
switch behavior — compose the layer residually). Routing/combination is
differentiable; the router's gate probability scales the expert output so
gradients reach the router (straight-through on the argmax path is not
needed for top-1 switch training).

``moe_ffn_reference`` computes the same capacity-limited semantics
densely on one device — the parity oracle for tests.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

_tm = jax.tree_util.tree_map


def _route(gate_logits: jax.Array, n_experts: int, capacity: int,
           k: int = 1):
    """Top-k routing with per-expert capacity on ONE device's tokens.

    Returns (expert_id (T, k), slot (T, k), keep (T, k), w (T, k)):
    ``slot`` is each (token, choice)'s position inside its expert's
    capacity buffer; ``keep`` is False for over-capacity entries.
    Capacity priority is choice-major (ALL first choices queue before any
    second choice — the GShard policy, so a token's secondary route never
    evicts another token's primary). Combine weights ``w``: the raw gate
    probability for k=1 (the switch convention, scales gradients into the
    router) and top-k-normalized probabilities for k>1 (GShard)."""
    prob_all = jax.nn.softmax(gate_logits, axis=-1)
    _, topi = lax.top_k(gate_logits, k)  # (T, k), distinct experts
    probk = jnp.take_along_axis(prob_all, topi, axis=1)  # (T, k)
    t = gate_logits.shape[0]
    ids_flat = topi.T.reshape(-1)  # choice-major: j=0 block first
    onehot = jax.nn.one_hot(ids_flat, n_experts, dtype=jnp.int32)
    # position of each entry within its expert's queue (0-based)
    slot = (jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
            ).reshape(k, t).T  # (T, k)
    keep = slot < capacity
    if k == 1:
        w = probk
    else:
        w = probk / jnp.maximum(
            jnp.sum(probk, axis=-1, keepdims=True), 1e-9)
    return topi, slot, keep, w


def moe_capacity(t_local: int, n_experts: int, capacity_factor: float,
                 k: int = 1) -> int:
    """Per-(source shard, expert) buffer size — one definition shared by
    the sharded path, the dense module path and the oracle so their
    drop behavior stays identical. Scales with k (each token consumes up
    to k slots, the GShard sizing)."""
    return max(1, math.ceil(t_local / n_experts * capacity_factor * k))


def moe_ffn(
    router_w: jax.Array,
    expert_params,
    expert_fn: Callable[[Any, jax.Array], jax.Array],
    x: jax.Array,
    mesh: Mesh,
    axis: str = "expert",
    capacity_factor: float = 1.25,
    router_top_k: int = 1,
    batch_axis: Optional[str] = None,
):
    """Expert-parallel top-k MoE over batch-sharded tokens.

    Args:
        router_w: (D, E) gate weights (replicated).
        expert_params: pytree with leading dim E (expert-stacked), sharded
            on ``axis`` — each device owns ONE expert's weights.
        expert_fn: ``(params_one_expert, tokens (N, D)) -> (N, D)``.
        x: (B, D) global token batch; B divisible by E (by dp*E with a
            ``batch_axis``).
        capacity_factor: per-expert buffer =
            ``moe_capacity(local_tokens, E, cf, k)``.
        router_top_k: 1 = switch (raw-gate-prob scaling), 2 = GShard
            (normalized top-2 combine weights).
        batch_axis: dp x ep composition — tokens shard over BOTH axes
            (``P((batch_axis, axis))``) and the ``all_to_all`` hops stay
            within each data row's expert group. Note the capacity
            accounting then runs per (data row, source device): dp*E
            source shards of b/(dp*E) tokens, NOT the E shards the
            expert-only layout (and the dense oracle) sees — identical
            math only when nothing exceeds capacity.

    Returns (B, D): combine-weighted expert outputs; dropped entries
    contribute 0.
    """
    n_experts = mesh.shape[axis]
    b, d = x.shape
    k = router_top_k
    if not 1 <= k <= n_experts:
        raise ValueError(f"router_top_k {k} not in [1, {n_experts}]")
    if router_w.shape[1] != n_experts:
        raise ValueError(
            f"router_w routes over {router_w.shape[1]} experts but the "
            f"{axis!r} mesh axis has {n_experts} — an oversized router "
            "would silently corrupt over-range tokens")
    if batch_axis is not None:
        if batch_axis == axis:
            raise ValueError(f"batch_axis must differ from expert axis "
                             f"{axis!r}")
        if batch_axis not in mesh.shape:
            raise ValueError(
                f"batch_axis {batch_axis!r} not in mesh axes "
                f"{tuple(mesh.shape)}")
    dp = mesh.shape[batch_axis] if batch_axis is not None else 1
    if b % (dp * n_experts):
        raise ValueError(
            f"batch {b} not divisible by data({dp}) x experts({n_experts})")
    for leaf in jax.tree_util.tree_leaves(expert_params):
        if leaf.shape[0] != n_experts:
            raise ValueError(
                f"expert_params leading dim {leaf.shape[0]} != experts "
                f"{n_experts}")
    t_local = b // (dp * n_experts)
    capacity = moe_capacity(t_local, n_experts, capacity_factor, k)

    def per_device(router_w, params_local, x_local):
        p = _tm(lambda a: a[0], params_local)
        logits = x_local @ router_w  # (T, E)
        expert_id, slot, keep, w = _route(logits, n_experts, capacity, k)

        # pack tokens into the (E, C, D) send buffer: row e = the tokens
        # this device routes to expert e, in arrival order; each token
        # writes one entry per kept routing choice
        send = jnp.zeros((n_experts, capacity, d), x_local.dtype)
        send = send.at[expert_id, slot].add(
            jnp.where(keep[..., None], x_local[:, None, :], 0.0))
        # all_to_all: axis e of send becomes the SOURCE axis on receipt —
        # recv[(s, c)] = tokens source device s routed to MY expert
        recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                              tiled=True)
        out = expert_fn(p, recv.reshape(n_experts * capacity, d))
        back = lax.all_to_all(out.reshape(n_experts, capacity, d), axis,
                              split_axis=0, concat_axis=0, tiled=True)
        # unpack: token i sums w_j * back[expert_id[i,j], slot[i,j]]
        gathered = back[expert_id, jnp.clip(slot, 0, capacity - 1)]
        y_local = jnp.sum(
            jnp.where(keep[..., None], gathered, 0.0) * w[..., None], axis=1)
        return y_local

    x_spec = P((batch_axis, axis)) if batch_axis is not None else P(axis)
    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(), P(axis), x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(router_w, expert_params, x)


def moe_ffn_reference(router_w, expert_params, expert_fn, x,
                      n_experts: int, capacity_factor: float = 1.25,
                      router_top_k: int = 1):
    """Dense single-device oracle with IDENTICAL routing semantics,
    including the per-source-device capacity accounting (tokens are
    capacity-limited within each batch shard, as the sharded layout
    drops them) and top-k combine weighting."""
    b, d = x.shape
    k = router_top_k
    if b % n_experts:
        raise ValueError(f"batch {b} not divisible by experts {n_experts}")
    t_local = b // n_experts
    capacity = moe_capacity(t_local, n_experts, capacity_factor, k)
    out = jnp.zeros_like(x)
    for s in range(n_experts):  # per source shard
        xs = x[s * t_local:(s + 1) * t_local]
        logits = xs @ router_w
        expert_id, slot, keep, w = _route(logits, n_experts, capacity, k)
        # j-independent: every expert's output over the whole shard, once
        per_expert = [
            expert_fn(_tm(lambda a, e=e: a[e], expert_params), xs)
            for e in range(n_experts)
        ]
        ys = jnp.zeros_like(xs)
        for j in range(k):
            yj = jnp.zeros_like(xs)
            for e in range(n_experts):
                mask = (expert_id[:, j] == e) & keep[:, j]
                yj = jnp.where(mask[:, None], per_expert[e], yj)
            # yj is already zero wherever keep[:, j] is False (every mask
            # ANDs it in)
            ys = ys + yj * w[:, j, None]
        out = out.at[s * t_local:(s + 1) * t_local].set(ys)
    return out
