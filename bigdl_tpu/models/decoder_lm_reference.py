"""Plain float32 reference of the decoder-only language model that
``bigdl_tpu.models.decoder_lm`` builds: forward pass, loss and ``jax.grad``
in straightforward ``jax.numpy``. No kernel, no sort, no cache, no batching:
dense masked attention, a loop over the experts held with a mask.

The equations (sizes from the catalog-style config dict, see ``decoder_lm``):

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; after the
  last block a final RMSNorm, then ``logits = y @ W_head``.
  ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * g``.
* attention: ``q = x W_q``, ``k = x W_k``, ``v = x W_v``; per-head RMSNorm
  with a learned gain on q and on k before RoPE; RoPE rotates the pairs
  ``(i, i + d/2)`` by ``pos * inv_freq_i`` and multiplies cos and sin by a
  factor; query head ``h`` reads K/V head ``h // (Hq / Hkv)``; scores
  ``q k^T / sqrt(d)``; query ``i`` sees key ``j`` iff ``j <= i`` and, on a
  sliding layer, ``i - j < window``.
* experts: ``p = softmax(x W_r)`` over all experts; the k largest;
  ``w_e = p_e / sum_chosen p`` (``norm_topk_prob``);
  ``MoE(x) = sum_{e chosen and held} w_e W_down,e(silu(W_gate,e x) * W_up,e x)``.
  Pairs routed to experts that are not held contribute nothing: the chip's
  share of an expert-parallel layer, without its exchange.
* loss: mean over positions of the cross-entropy of ``logits[t]`` against
  ``labels[t]`` (the caller shifts: the label of position t is token t + 1).

Departures from the published description of the first model built on this
(Mellum2-12B-A2.5B-Instruct), each **assumed**:

* the per-head RMSNorm on q and k has no key in that config; its key set is
  Qwen3-MoE's, where the norm is unconditional;
* no auxiliary load-balancing loss (the config gives no coefficient);
* the "MTP head" its model card mentions has no key in the config and is
  left out: the config wins.

Parameters, one float32 array each::

    {"embed": (V, D), "final_norm": (D,), "head": (D, V),
     "layers": [{"ln1": (D,), "wq": (D, Hq*d), "wk": (D, Hkv*d),
                 "wv": (D, Hkv*d), "q_norm": (d,), "k_norm": (d,),
                 "wo": (Hq*d, D), "ln2": (D,), "router": (D, E),
                 "w_gate": (E_held, D, F), "w_up": (E_held, D, F),
                 "w_down": (E_held, F, D)}, ...]}

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


def rotate(x, inv_freq, factor):
    """x (heads, T, d): pairs (i, i + d/2) rotated by pos * inv_freq_i."""
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


def experts(x, lp, cfg):
    """x (T, D) -> (this share's part of the expert layer's result (T, D),
    routed pairs per held expert (E_held,)). A loop over the experts held,
    each over ALL tokens with a mask; ``lax.scan`` and not a Python loop only
    so that the TPU compiles one expert's body, not sixteen."""
    operands = cfg.get("operands")
    p = jax.nn.softmax(x @ lp["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    def one_expert(out, expert):
        e, w_gate, w_up, w_down = expert
        chosen = top_e == e                                    # (T, k)
        w = jnp.sum(jnp.where(chosen, top_p, 0.0), axis=-1)    # (T,)
        h = jax.nn.silu(product("td,df->tf", x, w_gate, operands)) \
            * product("td,df->tf", x, w_up, operands)
        return (out + w[:, None] * product("tf,fd->td", h, w_down, operands),
                jnp.sum(chosen))

    held = jnp.asarray(cfg["experts_held"], top_e.dtype)
    return jax.lax.scan(one_expert, jnp.zeros_like(x),
                        (held, lp["w_gate"], lp["w_up"], lp["w_down"]))


def layer(x, lp, cfg, kind: str, block_q: int):
    """One block over one record: x (T, D) -> (y (T, D), routed pairs per
    held expert (E_held,))."""
    eps = cfg["rms_norm_eps"]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    t = x.shape[0]
    project = functools.partial(product, "td,de->te",
                                operands=cfg.get("operands"))
    inv_freq, factor = rope_inv_freq(cfg["rope_parameters"][kind], d)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    y = rms_norm(x, lp["ln1"], eps)
    q = project(y, lp["wq"]).reshape(t, hq, d).transpose(1, 0, 2)
    k = project(y, lp["wk"]).reshape(t, hkv, d).transpose(1, 0, 2)
    v = project(y, lp["wv"]).reshape(t, hkv, d).transpose(1, 0, 2)
    # assumed, see the module docstring
    q, k = rms_norm(q, lp["q_norm"], eps), rms_norm(k, lp["k_norm"], eps)
    q, k = rotate(q, inv_freq, factor), rotate(k, inv_freq, factor)
    a = attention(q, k, v, window, block_q, cfg.get("operands"))
    x = x + project(a.transpose(1, 0, 2).reshape(t, hq * d), lp["wo"])
    m, counts = experts(rms_norm(x, lp["ln2"], eps), lp, cfg)
    return x + m, counts


def forward(params, tokens, cfg, block_q: int = 512):
    """One record: tokens (T,) int -> (logits (T, V), routed pairs per layer
    and held expert (L, E_held)). Each layer is recomputed in the backward
    pass (``jax.checkpoint``), so that one layer's activations are live at a
    time at T = 8192."""
    x = params["embed"][tokens]
    counts = []
    for kind, lp in zip(cfg["layer_types"], params["layers"]):
        x, c = jax.checkpoint(
            lambda x, lp, kind=kind: layer(x, lp, cfg, kind, block_q))(x, lp)
        counts.append(c)
    logits = product(
        "td,dv->tv", rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]),
        params["head"], cfg.get("operands"))
    return logits, jnp.stack(counts)


def record_loss(params, tokens, labels, cfg, block_q: int = 512, at=None):
    """Summed cross-entropy of one record; beside it the routing counts and,
    where ``at`` names positions, the logits there."""
    logits, counts = forward(params, tokens, cfg, block_q)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked), (counts, None if at is None else logits[at])


def loss_and_grad(params, tokens, labels, cfg, block_q: int = 512, at=None):
    """Mean cross-entropy over a batch (N, T), its gradient, the routing
    counts (L, E_held) summed over the batch and the logits at the positions
    ``at`` (N, m) of each record (or None): record by record, so that one
    record's activations are live at a time."""
    n, t = tokens.shape
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, x, y, a: record_loss(p, x, y, cfg, block_q, a), has_aux=True))
    total, grads, counts, logits = 0.0, None, 0, []
    for i in range(n):
        (l, (c, z)), g = grad_fn(params, tokens[i], labels[i],
                                 None if at is None else at[i])
        total, counts = total + l, counts + c
        logits.append(z)
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    scale = 1.0 / (n * t)
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    return (total * scale, grads, counts,
            None if at is None else jnp.stack(logits))


def routing_counters(counts):
    """The step's three counters from (L, E_held) routed-pair counts:
    pairs that hit a held expert (summed over layers), the worst layer's
    load max over mean, and dropped pairs (none: nothing is ever dropped)."""
    counts = jnp.asarray(counts, jnp.float32)
    load = jnp.max(counts, axis=-1) / jnp.maximum(jnp.mean(counts, axis=-1), 1.0)
    return {"moe_pairs_local": float(jnp.sum(counts)),
            "moe_load_max_over_mean": float(jnp.max(load)),
            "moe_dropped_pairs": 0.0}
