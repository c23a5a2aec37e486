"""Plain float32 reference of the latent-attention, sparse-expert language
model with a multi-token-prediction module that
``bigdl_tpu.models.decoder_lm`` builds from DeepSeek-V3's key set
(JoyAI-LLM-Flash is the first model on it): forward pass, loss, ``jax.grad``,
the router's bias update and the routing counters in straightforward
``jax.numpy``. No kernel, no sort, no cache, no batching: dense masked
attention by query blocks, a loop over the experts held with a mask.

The equations (sizes from the config dict; ``x_0 = E[token]``):

* block ``l``: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``;
  ``FFN_l`` is a dense gated MLP (``(silu(a) * b) W_out`` with ``[a, b] = x
  W_in``) where the layer's parameters hold ``w_in``, the expert layer where
  they hold ``router``. After the last block a final RMSNorm, then
  ``logits = y W_head``. ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * g``.
* latent attention: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> H heads of
  ``[q_nope, q_rope]``; ``[c_kv, k_rope] = x W_kva``, ``c_kv = RMSNorm(c_kv)``,
  ``[k_nope, v]`` per head ``= c_kv W_kvb``. RoPE (theta ``rope_theta``, no
  scaling) turns ``q_rope`` per head and ``k_rope`` once, ALL HEADS SHARE IT,
  over the interleaved pairs ``(2i, 2i+1)`` (``rope_interleave``; half-split
  pairs ``(i, i + d/2)`` where false). ``k = [k_nope, k_rope]``; scores
  ``q k^T * softmax_scale`` (``1 / sqrt(nope + rope)``), causal, full; output
  heads of ``v_head_dim``; ``out = concat W_o``.
* router (``scoring_func`` sigmoid, ``topk_method`` noaux_tc, no groups):
  ``s = sigmoid(x W_r)`` over all experts; the k with the largest ``s + b``
  are chosen; their weights are ``s`` (without ``b``) over the chosen,
  divided by their sum + 1e-20, times ``routed_scaling_factor``.
  ``FFN(x) = sum_{e chosen and held} w_e Expert_e(x) + Shared(x)``, all gated
  MLPs. Pairs routed to experts that are not held contribute nothing: the
  chip's share of an expert-parallel layer, without its exchange.
* the bias ``b`` (zero at the start) takes no gradient; after a training
  step ``b_e <- b_e + rate * sign(mean_e' c_e' - c_e)``, ``c_e`` the step's
  (token, choice) pairs that chose expert ``e``, over all experts.
* multi-token prediction (depth 1): with ``x_L`` the last block's output
  BEFORE the final norm and ``t'`` the record shifted left by one (its last
  position filled with id 0), ``h' = [RMSNorm_e(E[t']) ; RMSNorm_h(x_L)]
  W_eh``, one sparse block of its own, its own norm, the main model's head:
  ``logits_1[i]`` predicts token ``i + 2``.
* loss: ``CE(logits, y) + mtp_loss_weight * CE(logits_1[:-1], y[1:])``, each a
  mean over its positions (the caller shifts: ``y[t]`` is token ``t + 1``).

**Assumed** (the config names the mechanisms and not these): the bias update
rate (DeepSeek-V3, arXiv:2412.19437: 0.001), the MTP module's structure and
the loss weight (the same paper, sections 2.2 and 4.2), the id 0 in the
shifted record's last place (Megatron's convention), no auxiliary loss.

Parameters, one float32 array each::

    {"embed": (V, D), "final_norm": (D,), "head": (D, V),
     "layers": [{"ln1": (D,), "wq_a": (D, Rq), "q_norm": (Rq,),
                 "wq_b": (Rq, H*(dn+dr)), "wkv_a": (D, Rkv+dr),
                 "kv_norm": (Rkv,), "wkv_b": (Rkv, H*(dn+dv)),
                 "wo": (H*dv, D), "ln2": (D,),
                 dense: "w_in": (D, 2F), "w_out": (F, D)
                 sparse: "router": (D, E), "w_gate": (E_held, D, Fe),
                 "w_up": (E_held, D, Fe), "w_down": (E_held, Fe, D),
                 "shared_in": (D, 2Fs), "shared_out": (Fs, D)}, ...],
     "mtp": {"enorm": (D,), "hnorm": (D,), "eh_proj": (2D, D),
             "norm": (D,), "layer": {a sparse layer}}}

``biases`` is a list of (E,) arrays, one for each routed layer in order, the
MTP module's last.

Callers on a TPU wrap calls in ``jax.default_matmul_precision("highest")``.
``cfg["operands"]`` (a dtype name) rounds both operands of every matrix
product but the router's to that dtype and still sums in float32, forward
and backward: the reading "bfloat16 operands, float32 accumulation".
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def product(spec, a, b, operands=None):
    """``einsum(spec, a, b)``; with ``operands`` (a dtype name) both are
    rounded to it first, the sum stays in their own dtype."""
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


def rotate(x, theta: float, interleaved: bool):
    """x (..., T, d): pairs (2i, 2i+1) (``interleaved``) or (i, i + d/2)
    rotated by ``pos * theta^(-2i/d)``, each left in its place."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention(q, k, v, scale: float, block_q: int, operands=None):
    """q, k (H, T, dq), v (H, T, dv) -> (H, T, dv), causal. One block of
    queries at a time so that the (H, block, T) scores fit at T = 8192; the
    block is recomputed in the backward pass for the same reason."""
    h, t, _ = q.shape
    block_q = min(block_q, t)
    if t % block_q:
        raise ValueError(f"T={t} is not a multiple of the query block {block_q}")

    @jax.checkpoint
    def one_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block_q, block_q, axis=1)
        s = product("hqd,hkd->hqk", qb, k, operands) * scale
        rows = i * block_q + jnp.arange(block_q)[:, None]
        seen = jnp.arange(t)[None, :] <= rows
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return product("hqk,hkd->hqd", p, v, operands)

    out = jax.lax.map(one_block, jnp.arange(t // block_q))  # (nb, H, bq, dv)
    return jnp.moveaxis(out, 0, 1).reshape(h, t, v.shape[-1])


def latent_attention(y, lp, cfg, block_q: int):
    """y (T, D), already normed -> (T, D)."""
    eps, operands = cfg["rms_norm_eps"], cfg.get("operands")
    h = cfg["num_attention_heads"]
    rank = cfg["kv_lora_rank"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    t = y.shape[0]
    project = functools.partial(product, "td,de->te", operands=operands)
    turn = functools.partial(rotate, theta=float(cfg["rope_theta"]),
                             interleaved=bool(cfg.get("rope_interleave", True)))
    c_q = rms_norm(project(y, lp["wq_a"]), lp["q_norm"], eps)
    q = project(c_q, lp["wq_b"]).reshape(t, h, dn + dr).transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :dn], turn(q[..., dn:])], axis=-1)
    kv_a = project(y, lp["wkv_a"])
    c_kv = rms_norm(kv_a[:, :rank], lp["kv_norm"], eps)
    k_rope = turn(kv_a[:, rank:])                      # (T, dr): one head
    kv = project(c_kv, lp["wkv_b"]).reshape(t, h, -1).transpose(1, 0, 2)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope[None], (h, t, dr))], axis=-1)
    scale = cfg.get("softmax_scale", 1.0 / math.sqrt(dn + dr))
    a = attention(q, k, kv[..., dn:], scale, block_q, operands)
    return project(a.transpose(1, 0, 2).reshape(t, -1), lp["wo"])


def gated_mlp(x, w_in, w_out, operands=None):
    a, b = jnp.split(product("td,df->tf", x, w_in, operands), 2, axis=-1)
    return product("tf,fd->td", jax.nn.silu(a) * b, w_out, operands)


def route(x, router, bias, cfg):
    """x (T, D) -> (weights (T, k), expert ids (T, k))."""
    s = jax.nn.sigmoid(x @ router)
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(bias),
                             cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    if cfg.get("bias_in_weights"):   # a planted fault, never the model
        top_s = top_s + bias[top_e]
    w = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"], top_e


def experts(x, lp, bias, cfg):
    """x (T, D) -> (this share's part of the routed sum plus the shared
    expert (T, D), pairs that chose each expert of the router (E,)). A loop
    over the experts held, each over ALL tokens with a mask; ``lax.scan``
    only so that one expert's body is compiled, not sixteen."""
    operands = cfg.get("operands")
    top_w, top_e = route(x, lp["router"], bias, cfg)

    def one_expert(out, expert):
        e, w_gate, w_up, w_down = expert
        w = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)    # (T,)
        h = jax.nn.silu(product("td,df->tf", x, w_gate, operands)) \
            * product("td,df->tf", x, w_up, operands)
        return out + w[:, None] * product("tf,fd->td", h, w_down, operands), None

    held = jnp.asarray(cfg["experts_held"], top_e.dtype)
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                          (held, lp["w_gate"], lp["w_up"], lp["w_down"]))
    if cfg.get("shared_expert", True):
        out = out + gated_mlp(x, lp["shared_in"], lp["shared_out"], operands)
    counts = jnp.sum(jax.nn.one_hot(top_e.reshape(-1), lp["router"].shape[1],
                                    dtype=jnp.float32), axis=0)
    return out, counts


def layer(x, lp, bias, cfg, block_q: int):
    """One block over one record: x (T, D) -> (y (T, D), the router's pairs
    per expert (E,), or None for a dense layer)."""
    eps = cfg["rms_norm_eps"]
    x = x + latent_attention(rms_norm(x, lp["ln1"], eps), lp, cfg, block_q)
    y = rms_norm(x, lp["ln2"], eps)
    if "router" not in lp:
        return x + gated_mlp(y, lp["w_in"], lp["w_out"], cfg.get("operands")), None
    m, counts = experts(y, lp, bias, cfg)
    return x + m, counts


def forward(params, biases, tokens, cfg, block_q: int = 512):
    """One record: tokens (T,) int -> (logits (T, V), logits_1 (T, V) or
    None, pairs per routed layer and expert (L_routed, E)). Each layer is
    recomputed in the backward pass, so that one layer's activations are live
    at a time at T = 8192."""
    eps, operands = cfg["rms_norm_eps"], cfg.get("operands")
    biases = list(biases)
    one = jax.checkpoint(lambda x, lp, b: layer(x, lp, b, cfg, block_q))
    counts = []

    def run(x, lp):
        y, c = one(x, lp, biases.pop(0) if "router" in lp else None)
        if c is not None:
            counts.append(c)
        return y

    def head(x, norm):
        return product("td,dv->tv", rms_norm(x, norm, eps), params["head"],
                       operands)

    x = params["embed"][tokens]
    for lp in params["layers"]:
        x = run(x, lp)
    logits, logits_1 = head(x, params["final_norm"]), None
    if "mtp" in params:
        mp = params["mtp"]
        nxt = jnp.concatenate([tokens[1:], jnp.zeros_like(tokens[:1])])
        both = jnp.concatenate(
            [rms_norm(params["embed"][nxt], mp["enorm"], eps),
             rms_norm(x, mp["hnorm"], eps)], axis=-1)
        x1 = run(product("te,ed->td", both, mp["eh_proj"], operands),
                 mp["layer"])
        logits_1 = head(x1, mp["norm"])
    return logits, logits_1, jnp.stack(counts)


def _summed_ce(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.sum(lse - jnp.take_along_axis(logits, labels[:, None], -1)[:, 0])


def record_loss(params, biases, tokens, labels, cfg, n: int,
                block_q: int = 512, at=None):
    """One record's part of the batch loss (``n`` records in the batch);
    beside it the two summed cross-entropies, the routing counts and, where
    ``at`` names positions, both heads' logits there."""
    logits, logits_1, counts = forward(params, biases, tokens, cfg, block_q)
    t = tokens.shape[0]
    main = _summed_ce(logits, labels)
    loss, second, picked = main / (n * t), jnp.zeros(()), None
    if logits_1 is not None:
        second = _summed_ce(logits_1[:-1], labels[1:])
        loss = loss + float(cfg.get("mtp_loss_weight", 0.0)) * second / (
            n * (t - 1))
    if at is not None:
        picked = logits[at] if logits_1 is None else jnp.stack(
            [logits[at], logits_1[at]])
    return loss, (main, second, counts, picked)


def bias_update(bias, counts, rate: float):
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


def loss_and_grad(params, biases, tokens, labels, cfg, block_q: int = 512,
                  at=None):
    """The batch (N, T)'s loss, its gradient, ``stats`` (``counts`` (L_routed,
    E) summed over the batch, ``main_loss``, ``mtp_loss``, ``biases``: the
    biases after the step) and both heads' logits at the positions ``at``
    (N, m) of each record ((N, 2, m, V), or None): record by record, so that
    one record's activations are live at a time."""
    n, t = tokens.shape
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, x, y, a: record_loss(p, biases, x, y, cfg, n, block_q, a),
        has_aux=True))
    total, main, second, grads, counts, picked = 0.0, 0.0, 0.0, None, 0, []
    for i in range(n):
        (l, (m, s, c, z)), g = grad_fn(params, tokens[i], labels[i],
                                       None if at is None else at[i])
        total, main, second, counts = total + l, main + m, second + s, counts + c
        picked.append(z)
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    rate = float(cfg["bias_update_rate"])
    stats = {"counts": counts, "main_loss": main / (n * t),
             "mtp_loss": second / (n * (t - 1)),
             "biases": [bias_update(b.astype(jnp.float32), c, rate)
                        for b, c in zip(biases, counts.astype(jnp.float32))]}
    return total, grads, stats, None if at is None else jnp.stack(picked)


def routing_counters(stats, cfg):
    """The step's counters from ``loss_and_grad``'s ``stats``: pairs that
    hit a held expert (summed over the routed layers), the worst layer's load
    max over mean among the experts held, dropped pairs (none: nothing is
    ever dropped), the largest |b| after the step, the second cross-entropy."""
    held = jnp.asarray(cfg["experts_held"])
    counts = jnp.asarray(stats["counts"], jnp.float32)[:, held]
    load = jnp.max(counts, axis=-1) / jnp.maximum(jnp.mean(counts, axis=-1), 1.0)
    return {"moe_pairs_local": float(jnp.sum(counts)),
            "moe_load_max_over_mean": float(jnp.max(load)),
            "moe_dropped_pairs": 0.0,
            "moe_bias_abs_max": float(max(jnp.max(jnp.abs(b))
                                          for b in stats["biases"])),
            "mtp_loss": float(stats["mtp_loss"])}
