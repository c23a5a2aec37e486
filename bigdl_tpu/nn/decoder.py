"""A decoder-only language model assembled from a per-layer pattern.

``DecoderLM(vocab_size, hidden_size, layer_types=[...], ...)`` stacks one
``DecoderBlock`` per entry of ``layer_types`` (``"sliding_attention"`` or
``"full_attention"``), each wrapped in ``nn.Remat``::

    h = x + Attn_l(RMSNorm(x))          GroupedQueryAttention
    y = h + MoE_l(RMSNorm(h))           nn.RoutedExperts

then a final RMSNorm and an untied head. Attention is grouped-query with a
per-head RMSNorm on q and k, RoPE from a given inverse-frequency vector and
factor (plain or YaRN, ``rope_inv_freq``), causal, with a sliding window on
the layers that say so, through ``scaled_dot_product_attention`` (the flash
kernel on the TPU takes window and grouped heads as they are). Matrix
products take their operands in ``Engine``'s compute dtype and accumulate in
float32 (``precision.dot_acc32``); norm statistics, the router and softmax
are float32. No bias anywhere.

This is ROADMAP D1's shape, begun: ``nn.Transformer`` (one flat block steered
by strings) stays beside it until D1 merges the two.

Device time is attributed by ``jax.named_scope``: ``embed``, ``attn_proj``,
``attn_window`` / ``attn_full`` (the kernel call alone), ``moe_route``,
``moe_experts``, ``lm_head`` (docs/observability.md).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import precision
from .attention import apply_rotary, scaled_dot_product_attention
from .embedding import LookupTable
from .initialization import RandomNormal
from .module import AbstractModule, Container
from .moe import RoutedExperts
from .normalization import RMSNorm
from .remat import Remat

LAYER_KINDS = ("sliding_attention", "full_attention")


def rope_inv_freq(rope: Dict, head_dim: int):
    """-> (inverse frequencies (head_dim/2,) float32, cos/sin factor).

    ``rope_type`` ``default``: ``theta^(-2i/d)``, factor 1. ``yarn`` (Peng et
    al. 2023, arXiv:2309.00071, as the transformers library reckons it): with
    ``dim(b) = d ln(L0 / (2 pi b)) / (2 ln theta)``, ``lo = floor(dim(beta_fast))``
    and ``hi = ceil(dim(beta_slow))`` clipped to ``[0, d - 1]``, frequency i
    is kept below ``lo``, divided by ``factor`` above ``hi`` and blended
    linearly between; the factor on cos and sin is ``attention_factor`` where
    given, else ``0.1 ln(factor) + 1``."""
    half = head_dim // 2
    theta = float(rope["rope_theta"])
    base = theta ** (-np.arange(half, dtype=np.float64) / half)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return base.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: 'default' or 'yarn'")
    scale = float(rope["factor"])
    l0 = float(rope["original_max_position_embeddings"])

    def dim(beta: float) -> float:
        return head_dim * math.log(l0 / (2 * math.pi * beta)) / (
            2 * math.log(theta))

    lo = max(math.floor(dim(float(rope.get("beta_fast", 32)))), 0)
    hi = min(math.ceil(dim(float(rope.get("beta_slow", 1)))), head_dim - 1)
    ramp = np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    factor = rope.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(scale) + 1.0
    inv = (1.0 - ramp) * base + ramp * base / scale
    return inv.astype(np.float32), float(factor)


class GroupedQueryAttention(AbstractModule):
    """Causal self-attention, ``num_heads`` query heads over ``num_kv_heads``
    K/V heads of ``head_dim``: ``(N, T, D) -> (N, T, D)``. ``window`` makes it
    sliding-window attention; ``rope`` is the layer kind's entry of
    ``rope_parameters``. q and k pass a per-head RMSNorm with a learned gain
    before RoPE."""

    def __init__(self, num_heads: int, num_kv_heads: int, head_dim: int,
                 window: Optional[int] = None, rope: Optional[Dict] = None,
                 eps: float = 1e-6, init_std: float = 0.02):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads cannot share "
                             f"{num_kv_heads} K/V heads")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.window = head_dim, window
        self.init_std = init_std
        self._rope = rope_inv_freq(rope, head_dim) if rope else None
        self._norm = RMSNorm(head_dim, eps)  # statistics in float32

    def infer_shape(self, in_spec):
        return jax.ShapeDtypeStruct(tuple(in_spec.shape), in_spec.dtype)

    def _build(self, rng, in_spec):
        d_model = in_spec.shape[-1]
        hq, hkv = (self.num_heads * self.head_dim,
                   self.num_kv_heads * self.head_dim)
        ks = jax.random.split(rng, 4)
        normal = lambda k, shape: self.init_std * jax.random.normal(  # noqa: E731
            k, shape, jnp.float32)
        return {"wq": normal(ks[0], (d_model, hq)),
                "wk": normal(ks[1], (d_model, hkv)),
                "wv": normal(ks[2], (d_model, hkv)),
                "wo": normal(ks[3], (hq, d_model)),
                "q_norm": jnp.ones((self.head_dim,)),
                "k_norm": jnp.ones((self.head_dim,))}, {}

    def _heads(self, x, w, heads: int):
        n, t, _ = x.shape
        return precision.dot_acc32(x, w).reshape(
            n, t, heads, self.head_dim).transpose(0, 2, 1, 3)

    def _apply(self, params, state, x, training, rng):
        n, t, _ = x.shape
        with jax.named_scope("attn_proj"):
            q = self._heads(x, params["wq"], self.num_heads)
            k = self._heads(x, params["wk"], self.num_kv_heads)
            v = self._heads(x, params["wv"], self.num_kv_heads)
            q = self._norm._apply({"weight": params["q_norm"]}, {}, q,
                                  training, None)[0]
            k = self._norm._apply({"weight": params["k_norm"]}, {}, k,
                                  training, None)[0]
            if self._rope is not None:
                inv_freq, factor = self._rope
                positions = jnp.arange(t)
                q = apply_rotary(q, positions, inv_freq, factor)
                k = apply_rotary(k, positions, inv_freq, factor)
        with jax.named_scope("attn_window" if self.window else "attn_full"):
            ctx = scaled_dot_product_attention(
                q, k, v, causal=True, mask_q=True, window=self.window)
        with jax.named_scope("attn_proj"):
            ctx = ctx.transpose(0, 2, 1, 3).reshape(n, t, -1)
            return precision.dot_acc32(ctx, params["wo"]).astype(x.dtype), state


class DecoderBlock(Container):
    """``h = x + attn(ln1(x))``, ``y = h + experts(ln2(h))``."""

    def __init__(self, attn: GroupedQueryAttention, experts: AbstractModule,
                 eps: float = 1e-6):
        super().__init__(RMSNorm(eps=eps).set_name("ln1"),
                         attn.set_name("attn"),
                         RMSNorm(eps=eps).set_name("ln2"),
                         experts.set_name("experts"))

    def build(self, rng, in_spec):
        for i, m in enumerate(self.modules):
            m.build(jax.random.fold_in(rng, i), in_spec)
        self._built = True
        return in_spec

    def infer_shape(self, in_spec):
        return jax.ShapeDtypeStruct(tuple(in_spec.shape), in_spec.dtype)

    def _apply(self, params, state, x, training, rng):
        ln1, attn, ln2, experts = self.modules
        new_state: Dict = {}
        run = lambda m, v: self._child_apply(  # noqa: E731
            m, v, training, rng, params, state, new_state)
        h = x + run(attn, run(ln1, x))
        return h + run(experts, run(ln2, h)), new_state


class LMHead(AbstractModule):
    """``logits = x @ W`` (D -> vocabulary), no bias, float32 logits."""

    def __init__(self, vocab_size: int, init_std: float = 0.02):
        super().__init__()
        self.vocab_size, self.init_std = vocab_size, init_std

    def infer_shape(self, in_spec):
        return jax.ShapeDtypeStruct(
            tuple(in_spec.shape[:-1]) + (self.vocab_size,), jnp.float32)

    def _build(self, rng, in_spec):
        w = self.init_std * jax.random.normal(
            rng, (in_spec.shape[-1], self.vocab_size), jnp.float32)
        return {"weight": w}, {}

    def _apply(self, params, state, x, training, rng):
        with jax.named_scope("lm_head"):
            return precision.dot_acc32(x, params["weight"]), state


class DecoderLM(Container):
    """Decoder-only language model: int tokens (N, T) -> logits (N, T, V).

    Args:
        vocab_size, hidden_size: V and D (embedding and head untied).
        layer_types: one of ``LAYER_KINDS`` per layer.
        num_heads, num_kv_heads, head_dim: attention geometry.
        sliding_window: the window of the sliding layers.
        rope_parameters: ``{kind: rope dict}`` (``rope_inv_freq``).
        n_experts, experts_per_token, expert_size: router width, k, F.
        experts_held: ids of the experts this chip holds (default all).
    """

    def __init__(self, vocab_size: int, hidden_size: int,
                 layer_types: Sequence[str], num_heads: int,
                 num_kv_heads: int, head_dim: int, sliding_window: int,
                 rope_parameters: Dict[str, Dict], n_experts: int,
                 experts_per_token: int, expert_size: int,
                 experts_held=None, eps: float = 1e-6,
                 init_std: float = 0.02):
        super().__init__()
        bad = [k for k in layer_types if k not in LAYER_KINDS]
        if bad:
            raise ValueError(f"layer_types {bad}: each of {LAYER_KINDS}")
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        embed = LookupTable(vocab_size, hidden_size)
        embed.weight_init = RandomNormal(0.0, init_std)
        self.add(embed.set_name("embed"))
        for i, kind in enumerate(layer_types):
            block = DecoderBlock(
                GroupedQueryAttention(
                    num_heads, num_kv_heads, head_dim,
                    window=sliding_window if kind == "sliding_attention"
                    else None,
                    rope=rope_parameters.get(kind), eps=eps,
                    init_std=init_std),
                RoutedExperts(n_experts, expert_size, experts_per_token,
                              experts_held=experts_held, init_std=init_std),
                eps=eps).set_name("block")
            self.add(Remat(block).set_name(f"layer_{i}"))
        self.add(RMSNorm(eps=eps).set_name("final_norm"))
        self.add(LMHead(vocab_size, init_std).set_name("head"))

    def build(self, rng, in_spec):
        spec = in_spec
        for i, m in enumerate(self.modules):
            spec = m.build(jax.random.fold_in(rng, i), spec)
        self._built = True
        return spec

    def infer_shape(self, in_spec):
        return jax.ShapeDtypeStruct(
            tuple(in_spec.shape) + (self.vocab_size,), jnp.float32)

    def _apply(self, params, state, x, training, rng):
        new_state: Dict = {}
        run = lambda m, v: self._child_apply(  # noqa: E731
            m, v, training, rng, params, state, new_state)
        embed, *blocks, final_norm, head = self.modules
        with jax.named_scope("embed"):
            h = run(embed, x)
        for block in blocks:
            h = run(block, h)
        return run(head, run(final_norm, h)), new_state
