"""A decoder-only language model assembled from a per-layer pattern.

``DecoderLM(vocab_size, hidden_size, layer_types=[...], ...)`` stacks one
``DecoderBlock`` per entry of ``layer_types``, each wrapped in ``nn.Remat``::

    h = x + r * Mixer_l(RMSNorm(x))     GroupedQueryAttention |
                                        LatentAttention | Mamba2Mixer
    y = h + r * FFN_l(RMSNorm(h))       nn.RoutedExperts | GatedMLP

then a final RMSNorm and the head. A layer whose ``mlp_layer_types`` entry is
``"none"`` is ONE mixer behind one norm with one residual add
(``MixerBlock``: ``y = x + r * Mixer_l(RMSNorm(x))``), and the layer kind
``"experts"`` makes the routed experts such a layer's mixer: the models whose
every layer is a state-space mixer, attention or routed experts alone. The
layer kinds (``LAYER_KINDS``):
``"sliding_attention"`` and ``"full_attention"`` / ``"attention"`` are
grouped-query attention, causal, with a sliding window on the layers that say
so, through ``scaled_dot_product_attention`` (the flash kernel on the TPU
takes window, grouped heads and the score scale as they are); ``"mamba"`` is
the Mamba-2 mixer (``nn/ssm.py``, a chunked state-space scan);
``"latent_attention"`` is multi-head latent attention (``LatentAttention``:
low-rank q and k/v paths, q/k heads of a no-position part and a rotary part
that all heads share on the k side, v heads of their own size, through the
same kernel). Grouped-query attention
takes a per-head RMSNorm on q and k (``qk_norm``), RoPE from a given
inverse-frequency vector and factor (plain or YaRN, ``rope_inv_freq``; a
kind without an entry in ``rope_parameters`` has no positional encoding) and
the score scale ``1/sqrt(head_dim)`` unless ``attention_scale`` gives
another. The feed-forward is routed experts where the model has experts and
one gated MLP where it has none, or layer by layer what ``mlp_layer_types``
says (``"dense"`` / ``"sparse"``: leading dense layers before sparse ones;
``"none"``: no feed-forward in the block). The experts are gated (three
matrices) or ``relu2`` (two): ``router["form"]``, see ``nn.RoutedExperts``.
With ``mtp_modules`` 1 the model carries DeepSeek-V3's multi-token-prediction
module (``MultiTokenPredictor``) and returns ``Table(logits, logits_1)``:
the second predicts the token after next through one more block, using the
embedding and the head a second time (one leaf each, its gradient the sum of
its uses); ``nn.MultiTokenCrossEntropyCriterion`` is its loss. Four scalars
change the paths: the
embedding is multiplied by ``embedding_multiplier``, each residual branch by
``residual_multiplier`` (``r``), the logits divided by ``logits_divisor``;
each is left out of the program at its neutral value. The head is its own
matrix, or with ``tie_embeddings`` the embedding's transpose: one leaf used
twice, its gradient the sum of both uses.

Matrix products take their operands in ``Engine``'s compute dtype and
accumulate in float32 (``precision.dot_acc32``); norm statistics, the
router, softmax and the scan's decays are float32. No bias anywhere but the
mixer's conv.

This is ROADMAP D1's shape, begun: ``nn.Transformer`` (one flat block steered
by strings) stays beside it until D1 merges the two.

Device time is attributed by ``jax.named_scope``: ``embed``, ``attn_proj``,
``attn_window`` / ``attn_full`` (the kernel call alone), ``mla_proj``,
``ssm_proj`` (inside it ``ssm_gate_norm``), ``ssm_conv``, ``ssm_scan``,
``moe_route``, ``moe_experts``,
``moe_shared``, ``mlp``, ``mtp`` (around the whole module), ``lm_head``
(docs/observability.md).
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
from .module import AbstractModule, Container, run_child
from .moe import RoutedExperts
from .normalization import RMSNorm
from .remat import Remat
from .ssm import Mamba2Mixer
from ..utils.table import Table

# "attention" is "full_attention" under the name the hybrid models give it
LAYER_KINDS = ("sliding_attention", "full_attention", "attention", "mamba",
               "latent_attention", "experts")
MLP_KINDS = ("dense", "sparse", "none")


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
    ``rope_parameters`` (None: no positional encoding). With ``qk_norm`` q
    and k pass a per-head RMSNorm with a learned gain before RoPE. ``scale``
    multiplies the scores (None: ``1/sqrt(head_dim)``)."""

    def __init__(self, num_heads: int, num_kv_heads: int, head_dim: int,
                 window: Optional[int] = None, rope: Optional[Dict] = None,
                 eps: float = 1e-6, init_std: float = 0.02,
                 qk_norm: bool = True, scale: Optional[float] = None):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads cannot share "
                             f"{num_kv_heads} K/V heads")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.window = head_dim, window
        self.init_std, self.scale = init_std, scale
        self._rope = rope_inv_freq(rope, head_dim) if rope else None
        # statistics in float32
        self._norm = RMSNorm(head_dim, eps) if qk_norm else None

    def infer_shape(self, in_spec):
        return jax.ShapeDtypeStruct(tuple(in_spec.shape), in_spec.dtype)

    def _build(self, rng, in_spec):
        d_model = in_spec.shape[-1]
        hq, hkv = (self.num_heads * self.head_dim,
                   self.num_kv_heads * self.head_dim)
        ks = jax.random.split(rng, 4)
        normal = lambda k, shape: self.init_std * jax.random.normal(  # noqa: E731
            k, shape, jnp.float32)
        params = {"wq": normal(ks[0], (d_model, hq)),
                  "wk": normal(ks[1], (d_model, hkv)),
                  "wv": normal(ks[2], (d_model, hkv)),
                  "wo": normal(ks[3], (hq, d_model))}
        if self._norm is not None:
            params.update(q_norm=jnp.ones((self.head_dim,)),
                          k_norm=jnp.ones((self.head_dim,)))
        return params, {}

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
            if self._norm is not None:
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
                q, k, v, causal=True, mask_q=True, window=self.window,
                scale=self.scale)
        with jax.named_scope("attn_proj"):
            ctx = ctx.transpose(0, 2, 1, 3).reshape(n, t, -1)
            return precision.dot_acc32(ctx, params["wo"]).astype(x.dtype), state


class LatentAttention(AbstractModule):
    """Multi-head latent attention (DeepSeek-V2/V3's), causal, in the
    expanded form training runs: ``(N, T, D) -> (N, T, D)``.

    ``c_q = RMSNorm(x W_qa)`` (``q_rank``), ``q = c_q W_qb`` -> ``num_heads``
    heads of ``[q_nope (nope_dim), q_rope (rope_dim)]``; ``[c_kv (kv_rank),
    k_rope (rope_dim)] = x W_kva``, ``c_kv = RMSNorm(c_kv)``, ``[k_nope
    (nope_dim), v (v_dim)]`` per head ``= c_kv W_kvb``. RoPE turns ``q_rope``
    per head and ``k_rope`` ONCE: all heads share it. ``k = [k_nope,
    k_rope]``; scores ``q k^T / sqrt(nope_dim + rope_dim)``; the output heads
    have ``v_dim``; ``out = concat W_o``. ``rope``: a ``rope_inv_freq`` dict
    over ``rope_dim``; ``interleaved``: its pairs are (2i, 2i+1)."""

    def __init__(self, num_heads: int, q_rank: int, kv_rank: int,
                 nope_dim: int, rope_dim: int, v_dim: int, rope: Dict,
                 interleaved: bool = True, eps: float = 1e-6,
                 init_std: float = 0.02):
        super().__init__()
        self.num_heads, self.q_rank, self.kv_rank = num_heads, q_rank, kv_rank
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.interleaved, self.init_std = interleaved, init_std
        self._rope = rope_inv_freq(rope, rope_dim)
        self._rms = RMSNorm(eps=eps)  # statistics in float32

    def infer_shape(self, in_spec):
        return jax.ShapeDtypeStruct(tuple(in_spec.shape), in_spec.dtype)

    def _build(self, rng, in_spec):
        d_model, h = in_spec.shape[-1], self.num_heads
        ks = jax.random.split(rng, 5)
        normal = lambda k, shape: self.init_std * jax.random.normal(  # noqa: E731
            k, shape, jnp.float32)
        return {
            "wq_a": normal(ks[0], (d_model, self.q_rank)),
            "q_norm": jnp.ones((self.q_rank,)),
            "wq_b": normal(ks[1], (self.q_rank,
                                   h * (self.nope_dim + self.rope_dim))),
            "wkv_a": normal(ks[2], (d_model, self.kv_rank + self.rope_dim)),
            "kv_norm": jnp.ones((self.kv_rank,)),
            "wkv_b": normal(ks[3], (self.kv_rank,
                                    h * (self.nope_dim + self.v_dim))),
            "wo": normal(ks[4], (h * self.v_dim, d_model)),
        }, {}

    def _norm(self, x, gain):
        return self._rms._apply({"weight": gain}, {}, x, False, None)[0]

    def _apply(self, params, state, x, training, rng):
        n, t, _ = x.shape
        h, nope = self.num_heads, self.nope_dim
        inv_freq, factor = self._rope
        rotary = lambda a: apply_rotary(  # noqa: E731
            a, jnp.arange(t), inv_freq, factor, interleaved=self.interleaved)
        with jax.named_scope("mla_proj"):
            c_q = self._norm(precision.dot_acc32(x, params["wq_a"]),
                             params["q_norm"])
            q = precision.dot_acc32(c_q, params["wq_b"]).reshape(
                n, t, h, -1).transpose(0, 2, 1, 3)
            q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:])], -1)
            kv_a = precision.dot_acc32(x, params["wkv_a"])
            c_kv = self._norm(kv_a[..., :self.kv_rank], params["kv_norm"])
            k_rope = rotary(kv_a[..., None, :, self.kv_rank:])  # (N, 1, T, r)
            kv = precision.dot_acc32(c_kv, params["wkv_b"]).reshape(
                n, t, h, -1).transpose(0, 2, 1, 3)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope, (n, h, t, self.rope_dim))], -1)
            v = kv[..., nope:]
        with jax.named_scope("attn_full"):
            ctx = scaled_dot_product_attention(q, k, v, causal=True,
                                               mask_q=True)
        with jax.named_scope("mla_proj"):
            ctx = ctx.transpose(0, 2, 1, 3).reshape(n, t, -1)
            return precision.dot_acc32(ctx, params["wo"]).astype(x.dtype), state


class GatedMLP(AbstractModule):
    """``[a, b] = split(x W_in)``, ``(silu(a) * b) W_out``: ``(..., D) ->
    (..., D)`` through ``size``, no bias; the two halves of ``W_in`` are one
    matrix and one product."""

    def __init__(self, size: int, init_std: float = 0.02):
        super().__init__()
        self.size, self.init_std = size, init_std

    def infer_shape(self, in_spec):
        return jax.ShapeDtypeStruct(tuple(in_spec.shape), in_spec.dtype)

    def _build(self, rng, in_spec):
        d = in_spec.shape[-1]
        k_in, k_out = jax.random.split(rng)
        return {"w_in": self.init_std * jax.random.normal(
                    k_in, (d, 2 * self.size), jnp.float32),
                "w_out": self.init_std * jax.random.normal(
                    k_out, (self.size, d), jnp.float32)}, {}

    def _apply(self, params, state, x, training, rng):
        with jax.named_scope("mlp"):
            a, b = jnp.split(precision.dot_acc32(x, params["w_in"]), 2, axis=-1)
            return precision.dot_acc32(
                jax.nn.silu(a) * b, params["w_out"]).astype(x.dtype), state


# what a block calls its mixer and its feed-forward in the parameter tree
_CHILD_NAMES = {GroupedQueryAttention: "attn", LatentAttention: "attn",
                Mamba2Mixer: "ssm", RoutedExperts: "experts", GatedMLP: "mlp"}


class DecoderBlock(Container):
    """``h = x + r * mixer(ln1(x))``, ``y = h + r * ffn(ln2(h))`` with ``r``
    the ``residual_multiplier``; any mixer and any feed-forward that map
    ``(N, T, D)`` to itself."""

    def __init__(self, mixer: AbstractModule, ffn: AbstractModule,
                 eps: float = 1e-6, residual_multiplier: float = 1.0):
        super().__init__(RMSNorm(eps=eps).set_name("ln1"),
                         mixer.set_name(_CHILD_NAMES.get(type(mixer), "mixer")),
                         RMSNorm(eps=eps).set_name("ln2"),
                         ffn.set_name(_CHILD_NAMES.get(type(ffn), "ffn")))
        self.residual_multiplier = float(residual_multiplier)

    def build(self, rng, in_spec):
        for i, m in enumerate(self.modules):
            m.build(jax.random.fold_in(rng, i), in_spec)
        self._built = True
        return in_spec

    def infer_shape(self, in_spec):
        return jax.ShapeDtypeStruct(tuple(in_spec.shape), in_spec.dtype)

    def _apply(self, params, state, x, training, rng):
        ln1, mixer, ln2, ffn = self.modules
        new_state: Dict = {}
        run = lambda m, v: self._child_apply(  # noqa: E731
            m, v, training, rng, params, state, new_state)
        r = self.residual_multiplier
        scaled = lambda v: v if r == 1.0 else r * v  # noqa: E731
        h = x + scaled(run(mixer, run(ln1, x)))
        return h + scaled(run(ffn, run(ln2, h))), new_state


class MixerBlock(Container):
    """``y = x + r * mixer(ln(x))``: one norm, one mixer, one residual add;
    any mixer that maps ``(N, T, D)`` to itself, ``RoutedExperts`` among
    them."""

    def __init__(self, mixer: AbstractModule, eps: float = 1e-6,
                 residual_multiplier: float = 1.0):
        super().__init__(RMSNorm(eps=eps).set_name("ln"),
                         mixer.set_name(_CHILD_NAMES.get(type(mixer), "mixer")))
        self.residual_multiplier = float(residual_multiplier)

    build = DecoderBlock.build
    infer_shape = DecoderBlock.infer_shape

    def _apply(self, params, state, x, training, rng):
        ln, mixer = self.modules
        new_state: Dict = {}
        run = lambda m, v: self._child_apply(  # noqa: E731
            m, v, training, rng, params, state, new_state)
        r = self.residual_multiplier
        mixed = run(mixer, run(ln, x))
        return x + (mixed if r == 1.0 else r * mixed), new_state


class LMHead(AbstractModule):
    """``logits = x @ W / divisor`` (D -> vocabulary), no bias, float32
    logits. ``tied``: the module holds no matrix of its own; its container
    hands it the embedding's transpose as ``weight``."""

    def __init__(self, vocab_size: int, init_std: float = 0.02,
                 tied: bool = False, divisor: float = 1.0):
        super().__init__()
        self.vocab_size, self.init_std = vocab_size, init_std
        self.tied, self.divisor = tied, float(divisor)

    def infer_shape(self, in_spec):
        return jax.ShapeDtypeStruct(
            tuple(in_spec.shape[:-1]) + (self.vocab_size,), jnp.float32)

    def build(self, rng, in_spec):
        if not self.tied:
            return super().build(rng, in_spec)
        # nothing of its own to allocate, and no matrix to trace the product with
        self._params, self._state, self._grads = {}, {}, {}
        self._built = True
        return self.infer_shape(in_spec)

    def _build(self, rng, in_spec):
        w = self.init_std * jax.random.normal(
            rng, (in_spec.shape[-1], self.vocab_size), jnp.float32)
        return {"weight": w}, {}

    def _apply(self, params, state, x, training, rng):
        with jax.named_scope("lm_head"):
            logits = precision.dot_acc32(x, params["weight"])
            return (logits if self.divisor == 1.0
                    else logits / self.divisor), state


class _Projection(AbstractModule):
    """``x W`` (..., K) -> (..., D), no bias, float32 out."""

    def __init__(self, size: int, init_std: float = 0.02):
        super().__init__()
        self.size, self.init_std = size, init_std

    def infer_shape(self, in_spec):
        return jax.ShapeDtypeStruct(
            tuple(in_spec.shape[:-1]) + (self.size,), in_spec.dtype)

    def _build(self, rng, in_spec):
        return {"weight": self.init_std * jax.random.normal(
            rng, (in_spec.shape[-1], self.size), jnp.float32)}, {}

    def _apply(self, params, state, x, training, rng):
        return precision.dot_acc32(x, params["weight"]).astype(x.dtype), state


class MultiTokenPredictor(Container):
    """DeepSeek-V3's multi-token-prediction module (arXiv:2412.19437 section
    2.2), depth 1: from the main model's last hidden state ``h`` (before its
    final norm) and the embedding ``e`` of the NEXT token,
    ``h' = [RMSNorm_e(e) ; RMSNorm_h(h)] W_eh`` (2D -> D, the embedding
    first), one decoder block of its own, and a norm of its own:
    ``(h, e) -> (N, T, D)``. Embedding and head are the main model's:
    ``DecoderLM`` looks ``e`` up before and applies the head after.

    State: ``{"_counters": {"mtp_loss": 0}}`` beside its children's: the slot
    that ``nn.MultiTokenCrossEntropyCriterion`` reports the second
    cross-entropy under, filled by the optimizer's loss function (a
    criterion has no state of its own to carry it in)."""

    def __init__(self, hidden_size: int, block: AbstractModule,
                 eps: float = 1e-6, init_std: float = 0.02):
        super().__init__(RMSNorm(eps=eps).set_name("enorm"),
                         RMSNorm(eps=eps).set_name("hnorm"),
                         _Projection(hidden_size, init_std).set_name("eh_proj"),
                         Remat(block.set_name("block")).set_name("layer"),
                         RMSNorm(eps=eps).set_name("norm"))

    def build(self, rng, in_spec):
        wide = jax.ShapeDtypeStruct(
            tuple(in_spec.shape[:-1]) + (2 * in_spec.shape[-1],), in_spec.dtype)
        for i, m in enumerate(self.modules):
            m.build(jax.random.fold_in(rng, i), wide if i == 2 else in_spec)
        self._built = True
        return in_spec

    def get_state(self):
        return {**super().get_state(),
                "_counters": {"mtp_loss": jnp.zeros((), jnp.float32)}}

    def _apply(self, params, state, x, training, rng):
        h, e = x
        enorm, hnorm, eh_proj, layer, norm = self.modules
        new_state: Dict = {"_counters": state["_counters"]}
        run = lambda m, v: self._child_apply(  # noqa: E731
            m, v, training, rng, params, state, new_state)
        with jax.named_scope("mla_proj"):
            both = jnp.concatenate([run(enorm, e), run(hnorm, h)], axis=-1)
            h = run(eh_proj, both)
        return run(norm, run(layer, h)), new_state


class DecoderLM(Container):
    """Decoder-only language model: int tokens (N, T) -> logits (N, T, V).

    Args:
        vocab_size, hidden_size: V and D.
        layer_types: one of ``LAYER_KINDS`` per layer.
        num_heads, num_kv_heads, head_dim: attention geometry.
        sliding_window: the window of the sliding layers.
        rope_parameters: ``{kind: rope dict}`` (``rope_inv_freq``); a kind
            without an entry has no positional encoding.
        n_experts, experts_per_token, expert_size: router width, k, F; with
            ``n_experts`` 0 the feed-forward is one ``GatedMLP(mlp_size)``.
        mlp_layer_types: one of ``MLP_KINDS`` per layer (default: every
            layer sparse where the model has experts, dense where not, and
            ``"none"`` for an ``"experts"`` layer, which takes no other).
        experts_held: ids of the experts this chip holds (default all).
        router: further ``nn.RoutedExperts`` arguments (``scoring``,
            ``routed_scaling``, ``bias_update_rate``, ``shared_size``,
            ``form``).
        latent: the ``"latent_attention"`` layers' ``LatentAttention`` sizes
            (``q_rank``, ``kv_rank``, ``nope_dim``, ``rope_dim``, ``v_dim``,
            ``interleaved``).
        mtp_modules: 0, or 1 for a ``MultiTokenPredictor`` whose block is of
            the last layer's kinds; the model then returns ``Table(logits,
            logits_1)``.
        qk_norm, attention_scale: see ``GroupedQueryAttention``.
        mamba: the ``"mamba"`` layers' ``Mamba2Mixer`` arguments (``heads``,
            ``head_dim``, ``state``, ``conv``, ``chunk``, ``groups``).
        embedding_multiplier, residual_multiplier, logits_divisor: the
            module docstring's three scalars.
        tie_embeddings: the head is the embedding's transpose.
    """

    def __init__(self, vocab_size: int, hidden_size: int,
                 layer_types: Sequence[str], num_heads: int,
                 num_kv_heads: int, head_dim: int,
                 sliding_window: Optional[int] = None,
                 rope_parameters: Optional[Dict[str, Dict]] = None,
                 n_experts: int = 0, experts_per_token: int = 0,
                 expert_size: int = 0, experts_held=None, eps: float = 1e-6,
                 init_std: float = 0.02, mlp_size: int = 0,
                 qk_norm: bool = True,
                 attention_scale: Optional[float] = None,
                 mamba: Optional[Dict] = None,
                 embedding_multiplier: float = 1.0,
                 residual_multiplier: float = 1.0,
                 logits_divisor: float = 1.0, tie_embeddings: bool = False,
                 mlp_layer_types: Optional[Sequence[str]] = None,
                 router: Optional[Dict] = None,
                 latent: Optional[Dict] = None, mtp_modules: int = 0):
        super().__init__()
        bad = [k for k in layer_types if k not in LAYER_KINDS]
        if bad:
            raise ValueError(f"layer_types {bad}: each of {LAYER_KINDS}")
        if "mamba" in layer_types and not mamba:
            raise ValueError("a 'mamba' layer needs the mamba sizes")
        if "latent_attention" in layer_types and not latent:
            raise ValueError("a 'latent_attention' layer needs the latent sizes")
        if not n_experts and not mlp_size:
            raise ValueError("neither experts nor a dense MLP: give "
                             "n_experts or mlp_size")
        if mlp_layer_types is None:
            mlp_layer_types = [
                "none" if kind == "experts"
                else "sparse" if n_experts else "dense" for kind in layer_types]
        bad = [k for k in mlp_layer_types if k not in MLP_KINDS]
        if bad or len(mlp_layer_types) != len(layer_types):
            raise ValueError(f"mlp_layer_types {list(mlp_layer_types)}: one of "
                             f"{MLP_KINDS} for each of {len(layer_types)} layers")
        routed = "sparse" in mlp_layer_types or "experts" in layer_types
        if (routed and not n_experts) or (
                "dense" in mlp_layer_types and not mlp_size):
            raise ValueError("a 'sparse' or 'experts' layer needs n_experts, "
                             "a 'dense' one mlp_size")
        if any(kind == "experts" and mlp != "none"
               for kind, mlp in zip(layer_types, mlp_layer_types)):
            raise ValueError("an 'experts' layer is the routed experts alone: "
                             "its mlp_layer_types entry is 'none'")
        if mtp_modules not in (0, 1):
            raise ValueError(f"mtp_modules {mtp_modules}: 0 or 1")
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.embedding_multiplier = float(embedding_multiplier)
        self.tie_embeddings = tie_embeddings
        rope_parameters = rope_parameters or {}
        embed = LookupTable(vocab_size, hidden_size)
        embed.weight_init = RandomNormal(0.0, init_std)
        self.add(embed.set_name("embed"))
        last_mamba = max((i for i, k in enumerate(layer_types) if k == "mamba"),
                         default=None)

        def experts():
            return RoutedExperts(n_experts, expert_size, experts_per_token,
                                 experts_held=experts_held, init_std=init_std,
                                 **(router or {}))

        def block(i, kind, mlp_kind):
            if kind == "experts":
                mixer = experts()
            elif kind == "mamba":
                mixer = Mamba2Mixer(**mamba, eps=eps, init_std=init_std,
                                    report_state=i == last_mamba)
            elif kind == "latent_attention":
                mixer = LatentAttention(
                    num_heads, **latent, rope=rope_parameters[kind], eps=eps,
                    init_std=init_std)
            else:
                mixer = GroupedQueryAttention(
                    num_heads, num_kv_heads, head_dim,
                    window=sliding_window if kind == "sliding_attention"
                    else None,
                    rope=rope_parameters.get(kind), eps=eps,
                    init_std=init_std, qk_norm=qk_norm, scale=attention_scale)
            if mlp_kind == "none":
                return MixerBlock(mixer, eps=eps,
                                  residual_multiplier=residual_multiplier)
            ffn = experts() if mlp_kind == "sparse" \
                else GatedMLP(mlp_size, init_std)
            return DecoderBlock(mixer, ffn, eps=eps,
                                residual_multiplier=residual_multiplier)

        for i, (kind, mlp_kind) in enumerate(zip(layer_types, mlp_layer_types)):
            self.add(Remat(block(i, kind, mlp_kind).set_name("block"))
                     .set_name(f"layer_{i}"))
        self.add(RMSNorm(eps=eps).set_name("final_norm"))
        self.add(LMHead(vocab_size, init_std, tied=tie_embeddings,
                        divisor=logits_divisor).set_name("head"))
        self.mtp = None
        if mtp_modules:
            self.mtp = MultiTokenPredictor(
                hidden_size, block(None, layer_types[-1], mlp_layer_types[-1]),
                eps=eps, init_std=init_std).set_name("mtp")
            self.add(self.mtp)

    def build(self, rng, in_spec):
        spec = in_spec
        for i, m in enumerate(self.modules):
            if m is self.mtp:  # beside the head, not after it: hidden states in
                m.build(jax.random.fold_in(rng, i), hidden)
                continue
            if m.name() == "final_norm":
                hidden = spec
            spec = m.build(jax.random.fold_in(rng, i), spec)
        self._built = True
        return self.infer_shape(in_spec)

    def infer_shape(self, in_spec):
        logits = jax.ShapeDtypeStruct(
            tuple(in_spec.shape) + (self.vocab_size,), jnp.float32)
        return logits if self.mtp is None else Table({1: logits, 2: logits})

    def _apply(self, params, state, x, training, rng):
        new_state: Dict = {}
        run = lambda m, v: self._child_apply(  # noqa: E731
            m, v, training, rng, params, state, new_state)
        embed, *blocks, final_norm, head = [
            m for m in self.modules if m is not self.mtp]

        def embedded(tokens):
            h = self._child_apply(
                embed, tokens, training, rng, params, state, new_state)
            return (h if self.embedding_multiplier == 1.0
                    else h * self.embedding_multiplier)

        def logits_of(h):
            # tied: the one leaf's second use, its gradient the sum of both
            logits, new_state[head.name()] = run_child(
                head, {"weight": params[embed.name()]["weight"].T}
                if self.tie_embeddings else params[head.name()],
                state[head.name()], h, training, rng)
            return logits

        with jax.named_scope("embed"):
            h = embedded(x)
        for block in blocks:
            h = run(block, h)
        logits = logits_of(run(final_norm, h))
        if self.mtp is None:
            return logits, new_state
        with jax.named_scope("mtp"):
            # the record shifted left by one; its last position has no next
            # token (id 0 stands there, and the loss leaves the position out)
            shifted = jnp.concatenate([x[:, 1:], jnp.zeros_like(x[:, :1])], 1)
            with jax.named_scope("embed"):
                e_next = embedded(shifted)
            logits_1 = logits_of(run(self.mtp, (h, e_next)))
        return Table({1: logits, 2: logits_1}), new_state
