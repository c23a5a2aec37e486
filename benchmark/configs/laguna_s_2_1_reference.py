"""Plain float32 reference of the decoder-only language model that
``bigdl_tpu.models.decoder_lm`` builds: forward pass, loss and ``jax.grad``
in straightforward ``jax.numpy``. No kernel, no sort, no cache, no batching:
dense masked attention, a loop over the experts held with a mask.

The equations (sizes from the catalog-style config dict, see ``decoder_lm``):

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, the FFN
  ``MoE`` on a sparse layer and ``SwiGLU`` on a dense one
  (``cfg["mlp_layer_types"]``, default all sparse); after the last block a
  final RMSNorm, then ``logits = y @ W_head``.
  ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * g``;
  ``SwiGLU(x) = (silu(a) * b) W_out`` with ``[a, b] = x W_in``.
* attention, layer l with ``Hq_l`` query heads (``cfg["layer_heads"]``,
  default ``num_attention_heads`` in every layer): ``q = x W_q``,
  ``k = x W_k``, ``v = x W_v``; per-head RMSNorm with a learned gain on q
  and on k before RoPE; RoPE rotates the pairs ``(i, i + r/2)`` of the first
  ``r = partial_rotary_factor * d`` features (default all) by ``pos *
  inv_freq_i``, frequencies reckoned over ``r``, multiplies cos and sin by a
  factor and passes features ``r..d`` through untouched; query head ``h``
  reads K/V head ``h // (Hq_l / Hkv)``; scores ``q k^T / sqrt(d)``; query
  ``i`` sees key ``j`` iff ``j <= i`` and, on a sliding layer,
  ``i - j < window``. With ``cfg["attention_gate"]`` each head's output is
  multiplied by ``g = sigmoid(x W_g)`` (one scalar a token and head, x the
  normed input of q, k and v) before ``W_o``; ``attn_gate_mean`` is the mean
  of g over tokens, heads and layers.
* experts: ``p = softmax(x W_r)`` over all experts; the k largest;
  ``w_e = s p_e / sum_chosen p`` (``norm_topk_prob``, ``s`` the
  ``routed_scaling``, default 1);
  ``MoE(x) = sum_{e chosen and held} w_e W_down,e(silu(W_gate,e x) * W_up,e x)``
  plus, where the layer has one, the shared expert
  ``SwiGLU_shared(x)``, times ``sigmoid(x w_s)`` with
  ``cfg["shared_expert_gate"]``. Pairs routed to experts that are not held
  contribute nothing: the chip's share of an expert-parallel layer, without
  its exchange.
* loss: mean over positions of the cross-entropy of ``logits[t]`` against
  ``labels[t]`` (the caller shifts: the label of position t is token t + 1).

Departures from the published description of the first model built on this
(Mellum2-12B-A2.5B-Instruct), each **assumed**:

* the per-head RMSNorm on q and k has no key in that config; its key set is
  Qwen3-MoE's, where the norm is unconditional;
* no auxiliary load-balancing loss (the config gives no coefficient);
* the "MTP head" its model card mentions has no key in the config and is
  left out: the config wins.

and of the second (Laguna-S-2.1), each **assumed** where its config is
silent, its key set being Qwen3-MoE's and Qwen3-Next's:

* the router's scores are a softmax;
* the per-head q/k RMSNorm, as above;
* the shared expert passes a sigmoid gate of one scalar a token, as in both
  families that name ``shared_expert_intermediate_size``;
* the attention gate reads the normed x that feeds q, k and v;
* the partial rotary's YaRN frequencies are reckoned over the rotated 64
  features, as the transformers library does for a partial rotary, and the
  attention factor scales the rotated features alone.

Parameters, one float32 array each::

    {"embed": (V, D), "final_norm": (D,), "head": (D, V),
     "layers": [{"ln1": (D,), "wq": (D, Hq*d), "wk": (D, Hkv*d),
                 "wv": (D, Hkv*d), "q_norm": (d,), "k_norm": (d,),
                 "wo": (Hq*d, D), "ln2": (D,), "router": (D, E),
                 "w_gate": (E_held, D, F), "w_up": (E_held, D, F),
                 "w_down": (E_held, F, D)}, ...]}

and where the model has them: ``"wg": (D, Hq)`` (the attention gate);
``"shared_in": (D, 2 F_s)``, ``"shared_out": (F_s, D)``, ``"shared_gate":
(D, 1)`` (the shared expert); on a dense layer ``"w_in": (D, 2 F_d)``,
``"w_out": (F_d, D)`` in place of the router and the experts.

Callers on a TPU wrap calls in ``jax.default_matmul_precision("highest")``:
a float32 matrix product otherwise runs in one bf16 pass there.

**At a stated precision.** A configuration that states "bfloat16 operands,
float32 accumulation" for its matrix products (projections, attention,
experts, head; never the router, softmax or norms) is held to exactly that
with ``cfg["operands"] = "bfloat16"``: every such product rounds both
operands to that dtype first and still sums in float32 (``product``), in the
forward pass and in the two products of its gradient, where the cotangent is
an operand too. Against this reading a system at the stated precision differs
by the order of its sums and by where its kernels round; one that also
rounds results, the router or the softmax differs by those roundings.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def rope_inv_freq(rope: dict, head_dim: int):
    """(inverse frequencies (head_dim/2,), cos/sin factor) of one layer kind.

    ``default``: ``theta^(-2i/d)``, factor 1. ``yarn`` (Peng et al. 2023, as
    the transformers library computes it): frequencies below ``lo`` are kept,
    above ``hi`` divided by ``factor``, blended linearly between, where
    ``d(beta) = d ln(L0 / (2 pi beta)) / (2 ln theta)``, ``lo = floor(d(beta_fast))``,
    ``hi = ceil(d(beta_slow))``; the factor is ``attention_factor`` if given,
    else ``0.1 ln(factor) + 1``."""
    half = head_dim // 2
    theta = float(rope["rope_theta"])
    base = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return base, 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: only 'default' and 'yarn'")
    s = float(rope["factor"])
    l0 = float(rope["original_max_position_embeddings"])

    def d(beta):
        return head_dim * math.log(l0 / (2 * math.pi * beta)) / (2 * math.log(theta))

    lo = max(math.floor(d(float(rope.get("beta_fast", 32)))), 0)
    hi = min(math.ceil(d(float(rope.get("beta_slow", 1)))), head_dim - 1)
    r = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo) / max(hi - lo, 1e-3),
                 0.0, 1.0)
    a = rope.get("attention_factor")
    if a is None:
        a = 0.1 * math.log(s) + 1.0
    return (1.0 - r) * base + r * base / s, float(a)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def product(spec, a, b, operands=None):
    """``einsum(spec, a, b)``; with ``operands`` (a dtype name) both are
    rounded to it first, the sum stays in their own dtype (module docstring)."""
    return jnp.einsum(spec, _rounded(a, operands), _rounded(b, operands))


def _rounded(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(x.dtype)


def _product_fwd(spec, a, b, operands):
    return product(spec, a, b, operands), (a, b)


def _product_bwd(spec, operands, operands_seen, g):
    a, b = operands_seen
    _, transposed = jax.vjp(functools.partial(jnp.einsum, spec),
                            _rounded(a, operands), _rounded(b, operands))
    return transposed(_rounded(g, operands))


product.defvjp(_product_fwd, _product_bwd)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotate(x, inv_freq, factor, rotary_dim=None):
    """x (heads, T, d): pairs (i, i + r/2) of the first ``rotary_dim`` = r
    features (default all) rotated by pos * inv_freq_i, the rest as they
    are."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        return jnp.concatenate([rotate(x[..., :rotary_dim], inv_freq, factor),
                                x[..., rotary_dim:]], axis=-1)
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention(q, k, v, window, block_q, operands=None):
    """q (Hq, T, d), k and v (Hkv, T, d) -> (Hq, T, d). One block of queries
    at a time so that the (Hq, block, T) scores fit at T = 8192; the block is
    recomputed in the backward pass (``jax.checkpoint``) for the same reason."""
    hq, t, d = q.shape
    group = hq // k.shape[0]
    kk, vv = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    block_q = min(block_q, t)
    if t % block_q:
        raise ValueError(f"T={t} is not a multiple of the query block {block_q}")

    @jax.checkpoint
    def one_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block_q, block_q, axis=1)
        s = product("hqd,hkd->hqk", qb, kk, operands) / math.sqrt(d)
        rows = i * block_q + jnp.arange(block_q)[:, None]
        cols = jnp.arange(t)[None, :]
        seen = cols <= rows
        if window is not None:
            seen = seen & (rows - cols < window)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return product("hqk,hkd->hqd", p, vv, operands)

    out = jax.lax.map(one_block, jnp.arange(t // block_q))  # (nb, Hq, bq, d)
    return jnp.moveaxis(out, 0, 1).reshape(hq, t, d)


def swiglu(x, w_in, w_out, operands=None):
    """``(silu(a) * b) W_out`` with ``[a, b] = x W_in``: x (T, D) -> (T, D)."""
    a, b = jnp.split(product("td,df->tf", x, w_in, operands), 2, axis=-1)
    return product("tf,fd->td", jax.nn.silu(a) * b, w_out, operands)


def experts(x, lp, cfg):
    """x (T, D) -> (this share's part of the expert layer's result (T, D),
    routed pairs per held expert (E_held,)). A loop over the experts held,
    each over ALL tokens with a mask; ``lax.scan`` and not a Python loop only
    so that the TPU compiles one expert's body, not sixteen. The shared
    expert, where the layer has one, is every share's alike."""
    operands = cfg.get("operands")
    p = jax.nn.softmax(x @ lp["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if cfg.get("routed_scaling", 1.0) != 1.0:
        top_p = top_p * cfg["routed_scaling"]

    def one_expert(out, expert):
        e, w_gate, w_up, w_down = expert
        chosen = top_e == e                                    # (T, k)
        w = jnp.sum(jnp.where(chosen, top_p, 0.0), axis=-1)    # (T,)
        h = jax.nn.silu(product("td,df->tf", x, w_gate, operands)) \
            * product("td,df->tf", x, w_up, operands)
        return (out + w[:, None] * product("tf,fd->td", h, w_down, operands),
                jnp.sum(chosen))

    held = jnp.asarray(cfg["experts_held"], top_e.dtype)
    out, counts = jax.lax.scan(one_expert, jnp.zeros_like(x),
                               (held, lp["w_gate"], lp["w_up"], lp["w_down"]))
    if "shared_in" in lp:
        shared = swiglu(x, lp["shared_in"], lp["shared_out"], operands)
        if cfg.get("shared_expert_gate"):
            shared = shared * jax.nn.sigmoid(
                product("td,de->te", x, lp["shared_gate"], operands))
        out = out + shared
    return out, counts


def layer(x, lp, cfg, kind: str, block_q: int, hq=None, mlp="sparse"):
    """One block over one record with ``hq`` query heads (default
    ``num_attention_heads``) and a ``"sparse"`` or ``"dense"`` feed-forward:
    x (T, D) -> (y (T, D), routed pairs per held expert (E_held,) or None,
    the attention gate's mean or None)."""
    eps = cfg["rms_norm_eps"]
    hkv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    hq = cfg["num_attention_heads"] if hq is None else hq
    t = x.shape[0]
    project = functools.partial(product, "td,de->te",
                                operands=cfg.get("operands"))
    rope = cfg["rope_parameters"][kind]
    r = round(d * float(rope.get("partial_rotary_factor", 1)))
    inv_freq, factor = rope_inv_freq(rope, r)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    y = rms_norm(x, lp["ln1"], eps)
    q = project(y, lp["wq"]).reshape(t, hq, d).transpose(1, 0, 2)
    k = project(y, lp["wk"]).reshape(t, hkv, d).transpose(1, 0, 2)
    v = project(y, lp["wv"]).reshape(t, hkv, d).transpose(1, 0, 2)
    # assumed, see the module docstring
    q, k = rms_norm(q, lp["q_norm"], eps), rms_norm(k, lp["k_norm"], eps)
    q, k = rotate(q, inv_freq, factor, r), rotate(k, inv_freq, factor, r)
    a = attention(q, k, v, window, block_q, cfg.get("operands"))
    a, gate_mean = a.transpose(1, 0, 2), None                  # (T, Hq, d)
    if cfg.get("attention_gate"):
        g = jax.nn.sigmoid(project(y, lp["wg"]))              # (T, Hq)
        a, gate_mean = a * g[..., None], jnp.mean(g)
    x = x + project(a.reshape(t, hq * d), lp["wo"])
    h = rms_norm(x, lp["ln2"], eps)
    if mlp == "dense":
        return x + swiglu(h, lp["w_in"], lp["w_out"], cfg.get("operands")), \
            None, gate_mean
    m, counts = experts(h, lp, cfg)
    return x + m, counts, gate_mean


def forward(params, tokens, cfg, block_q: int = 512):
    """One record: tokens (T,) int -> (logits (T, V), stats): ``counts``, the
    routed pairs per sparse layer and held expert (L_sparse, E_held), and,
    with ``cfg["attention_gate"]``, ``attn_gate_mean``. Each layer is
    recomputed in the backward pass (``jax.checkpoint``), so that one layer's
    activations are live at a time at T = 8192."""
    x = params["embed"][tokens]
    kinds = cfg["layer_types"]
    heads = cfg.get("layer_heads") or [cfg["num_attention_heads"]] * len(kinds)
    mlps = cfg.get("mlp_layer_types") or ["sparse"] * len(kinds)
    counts, gates = [], []
    for kind, lp, hq, mlp in zip(kinds, params["layers"], heads, mlps):
        x, c, g = jax.checkpoint(
            lambda x, lp, kind=kind, hq=hq, mlp=mlp: layer(
                x, lp, cfg, kind, block_q, hq, mlp))(x, lp)
        counts += [] if c is None else [c]
        gates += [] if g is None else [g]
    logits = product(
        "td,dv->tv", rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]),
        params["head"], cfg.get("operands"))
    stats = {"counts": jnp.stack(counts)}
    if gates:
        stats["attn_gate_mean"] = jnp.mean(jnp.stack(gates))
    return logits, stats


def record_loss(params, tokens, labels, cfg, block_q: int = 512, at=None):
    """Summed cross-entropy of one record; beside it ``forward``'s stats and,
    where ``at`` names positions, the logits there."""
    logits, stats = forward(params, tokens, cfg, block_q)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked), (stats, None if at is None else logits[at])


def loss_and_grad(params, tokens, labels, cfg, block_q: int = 512, at=None):
    """Mean cross-entropy over a batch (N, T), its gradient, the stats (the
    routing counts (L_sparse, E_held) summed over the batch, the attention
    gate's mean over it) and the logits at the positions ``at`` (N, m) of
    each record (or None): record by record, so that one record's
    activations are live at a time."""
    n, t = tokens.shape
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, x, y, a: record_loss(p, x, y, cfg, block_q, a), has_aux=True))
    total, grads, stats, logits = 0.0, None, None, []
    for i in range(n):
        (l, (s, z)), g = grad_fn(params, tokens[i], labels[i],
                                 None if at is None else at[i])
        total = total + l
        stats = s if stats is None else jax.tree_util.tree_map(jnp.add, stats, s)
        logits.append(z)
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    scale = 1.0 / (n * t)
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    if "attn_gate_mean" in stats:
        stats["attn_gate_mean"] = stats["attn_gate_mean"] / n
    return (total * scale, grads, stats,
            None if at is None else jnp.stack(logits))


def routing_counters(stats):
    """The step's counters from ``loss_and_grad``'s stats: of the (L, E_held)
    routed-pair counts, pairs that hit a held expert (summed over layers),
    the worst layer's load max over mean, and dropped pairs (none: nothing is
    ever dropped); and the attention gate's mean where the model has one."""
    counts = jnp.asarray(stats["counts"], jnp.float32)
    load = jnp.max(counts, axis=-1) / jnp.maximum(jnp.mean(counts, axis=-1), 1.0)
    out = {"moe_pairs_local": float(jnp.sum(counts)),
           "moe_load_max_over_mean": float(jnp.max(load)),
           "moe_dropped_pairs": 0.0}
    if "attn_gate_mean" in stats:
        out["attn_gate_mean"] = float(stats["attn_gate_mean"])
    return out
