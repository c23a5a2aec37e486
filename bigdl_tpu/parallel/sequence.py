"""Sequence/context parallelism: ring attention over an ICI mesh axis.

The reference has NO long-sequence story beyond ``Recurrent``'s O(T) time loop
(SURVEY.md §5 "Long-context / sequence parallelism: absent in reference") — this
module is a TPU-first capability extension, not a port: sequences are sharded
across devices on a ``sp`` mesh axis and attention runs as a ring, rotating K/V
blocks around the ICI torus with ``lax.ppermute`` while accumulating the exact
softmax online (the flash-attention recurrence, blocked at device granularity).

Memory per device drops from O(T^2) logits to O(T * T/n), and the K/V transfer
for step s+1 overlaps with the matmuls of step s (XLA schedules the ppermute
DMA concurrently with compute — the standard ring-overlap pattern on TPU).

Used directly (``ring_attention``) or per-shard inside a larger ``shard_map``
(``ring_attention_shard``).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


def ring_attention_shard(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    axis_size: int,
    causal: bool = False,
    scale: Optional[float] = None,
    lengths: Optional[jax.Array] = None,
    mask_q: Optional[bool] = None,
) -> jax.Array:
    """Exact attention over sequence shards; call inside ``shard_map``.

    ``q``/``k``/``v``: (N, heads, Tc, d) — the local sequence chunk, where the
    global sequence length is ``Tc * axis_size`` and device ``i`` holds chunk
    ``i`` (contiguous partition, matching ``PartitionSpec`` sharding of axis 2).

    ``causal`` masks with GLOBAL positions: query t on device i has global index
    ``i*Tc + t``. The K/V block visiting at ring step s originated on device
    ``(i - s) % n``, which determines the key offsets.

    ``lengths`` (int (N,), REPLICATED across the sp axis) is the padded-batch
    key mask in GLOBAL positions — the same contract as
    ``flash_attention(..., lengths=)``: keys at global index >= lengths[b] are
    invisible; with ``mask_q`` (``None`` resolves to the same Tq == Tk
    self-attention heuristic as the kernel — cross-attention callers pass
    ``mask_q=False`` explicitly) padded query rows produce zero output/grad.
    Trailing-pad only, like the kernel.
    """
    n = axis_size
    me = lax.axis_index(axis_name)
    nb, _, tc, depth = q.shape
    tk = k.shape[2]
    if mask_q is None:
        mask_q = tc == tk  # global Tq == Tk <=> local chunks equal
    if scale is None:
        scale = 1.0 / math.sqrt(depth)

    # global query positions, aligned at the END for rectangular Tq != Tk —
    # the same convention as flash_attention/scaled_dot_product_attention
    # (query t attends keys up to t + (Tk_global - Tq_global))
    q_pos = me * tc + jnp.arange(tc) + n * (tk - tc)

    m = jnp.full(q.shape[:3], -1e30, q.dtype)  # running row max
    l = jnp.zeros(q.shape[:3], q.dtype)  # running softmax denominator
    o = jnp.zeros_like(q)  # running weighted numerator

    perm = [(i, (i + 1) % n) for i in range(n)]

    for s in range(n):
        src = (me - s) % n  # which global block this k/v is
        k_pos = src * tk + jnp.arange(tk)  # global key positions
        logits = jnp.einsum("nhqd,nhkd->nhqk", q, k) * scale
        allowed = None  # boolean, broadcasts over (N, Tc, Tk)
        if causal:
            allowed = (q_pos[:, None] >= k_pos[None, :])[None]  # (1,Tc,Tk)
        if lengths is not None:
            key_ok = k_pos[None, None, :] < lengths[:, None, None]  # (N,1,Tk)
            allowed = key_ok if allowed is None else (allowed & key_ok)
        if allowed is not None:
            logits = jnp.where(allowed[:, None], logits, -jnp.inf)
        block_max = jnp.max(logits, axis=-1)  # (N,H,Tc), -inf if all masked
        m_new = jnp.maximum(m, block_max)
        # -inf logits -> exp 0; m_new stays finite (init -1e30) so no nan
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum("nhqk,nhkd->nhqd", p, v)
        m = m_new
        if s != n - 1:
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)

    out = o / jnp.maximum(l, 1e-30)[..., None]
    if lengths is not None and mask_q:
        row_valid = (q_pos[None, :] < lengths[:, None])  # (N, Tc)
        out = out * row_valid[:, None, :, None].astype(out.dtype)
    return out


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: jax.sharding.Mesh,
    axis_name: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
    lengths: Optional[jax.Array] = None,
    mask_q: Optional[bool] = None,
) -> jax.Array:
    """Global-view wrapper: shards the sequence axis (dim 2) of (N, heads, T, d)
    operands over ``mesh[axis_name]`` and runs the ring. Differentiable (the
    whole ring is traced; ``jax.grad`` derives the backward ring).

    ``lengths`` (int (N,)) carries per-sequence valid lengths in GLOBAL
    positions for padded batches — replicated to every sequence shard; same
    semantics as ``flash_attention(..., lengths=, mask_q=)`` including the
    ``mask_q=None`` → Tq == Tk self-attention heuristic."""
    n = mesh.shape[axis_name]
    if q.shape[2] % n or k.shape[2] % n:
        raise ValueError(
            f"sequence length {q.shape[2]}/{k.shape[2]} not divisible by "
            f"mesh axis {axis_name!r} size {n}"
        )
    spec = P(None, None, axis_name, None)
    shard_fn = partial(
        ring_attention_shard,
        axis_name=axis_name,
        axis_size=n,
        causal=causal,
        scale=scale,
        # resolve the heuristic HERE on global lengths; local chunks inside
        # shard_map see the same Tq == Tk relation but being explicit keeps
        # the contract independent of the sharding
        mask_q=(q.shape[2] == k.shape[2]) if mask_q is None else mask_q,
    )
    operands = (q, k, v)
    in_specs = (spec, spec, spec)
    if lengths is not None:
        shard_fn = partial((lambda f, qq, kk, vv, ll: f(qq, kk, vv,
                                                        lengths=ll)), shard_fn)
        operands = operands + (lengths,)
        in_specs = in_specs + (P(None),)  # lengths replicated
    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=spec,
        check_vma=False,
    )
    return fn(*operands)
