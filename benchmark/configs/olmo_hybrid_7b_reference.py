"""Plain float32 reference of the gated delta-rule / attention hybrid language
model that ``bigdl_tpu.models.decoder_lm`` builds from an Olmo-Hybrid-style
config: forward pass, loss and ``jax.grad`` in straightforward ``jax.numpy``,
record by record. No kernel, no chunked form, no cache, no batching: the
linear-attention layer is **the recurrence as written**, one token after
another; attention is dense, by blocks of queries.

The equations (sizes from the catalog-style config dict, see ``decoder_lm``):

* ``x_0 = E[token]``; block (Olmo 2's reordered norm): ``h = x +
  RMSNorm(Mixer(x))``, ``y = h + RMSNorm(MLP(h))``; ``logits = RMSNorm(x_L)
  W_head``. ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * g``. No bias
  anywhere.
* MLP: ``[a, b] = split(x W_in)``, ``(silu(a) * b) W_out``.
* attention (``full_attention``): ``q = RMSNorm(x W_q)``, ``k = RMSNorm(x
  W_k)`` each over the whole projection (Hq d and Hkv d wide, a gain as
  wide), ``v = x W_v``, **no positional encoding**; query head ``h`` reads
  K/V head ``h // (Hq / Hkv)``; scores ``q k^T / sqrt(d)``; query ``i`` sees
  key ``j`` iff ``j <= i``.
* Gated DeltaNet (``linear_attention``), ``H`` heads, keys ``dk``, values
  ``dv``: ``[q, k, v] = silu(conv([x W_q, x W_k, x W_v]))``, depthwise and
  causal WITHOUT a bias, ``conv(u)[t] = sum_k w[:, k] u[t - (K-1) + k]``,
  ``u`` zero before the record; per head ``q <- q / sqrt(|q|^2 + 1e-6)``,
  ``k`` the same; ``beta = 2 sigmoid(x W_b)`` (``linear_allow_neg_eigval``;
  else ``sigmoid``); ``alpha = exp(-exp(A_log) softplus(x W_a + dt_bias))``;
  ``S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T``
  (``S_0 = 0``, (dv, dk)), ``o_t = S_t q_t / sqrt(dk)``; ``out = (RMSNorm(o)
  * silu(x W_z)) W_o``, the norm over each head's dv with one gain of dv,
  FIRST, then the gate.
* loss: mean over positions of the cross-entropy of ``logits[t]`` against
  ``labels[t]`` (the caller shifts: the label of position t is token t + 1).

**Assumed** (the published config has no key for them; see
``benchmark/configs/olmo_hybrid_7b.json``'s ``assumed``): the Gated DeltaNet
equations for Qwen3-Next's ``linear_*`` keys, the scale ``1/sqrt(dk)``, the
output norm's eps the model's ``rms_norm_eps``, packed records with no
document mask (the state and the attention cross document boundaries).

Parameters, one float32 array each::

    {"embed": (V, D), "final_norm": (D,), "head": (D, V),
     "layers": [linear: {"ln1": (D,), "wq": (D, H dk), "wk": (D, H dk),
                         "wv": (D, H dv), "wz": (D, H dv), "wa": (D, H),
                         "wb": (D, H), "conv_w": (2 H dk + H dv, K),
                         "A_log": (H,), "dt_bias": (H,), "norm": (dv,),
                         "wo": (H dv, D), "ln2": (D,), "w_in": (D, 2 F),
                         "w_out": (F, D)}
                full: {"ln1", "wq": (D, Hq d), "wk": (D, Hkv d),
                       "wv": (D, Hkv d), "wo": (Hq d, D), "q_norm": (Hq d,),
                       "k_norm": (Hkv d,), "ln2", "w_in", "w_out"}, ...]}

Callers on a TPU wrap calls in ``jax.default_matmul_precision("highest")``:
a float32 matrix product otherwise runs in one bf16 pass there.

**At a stated precision.** ``cfg["operands"] = "bfloat16"`` holds the matrix
products of the projections, attention, MLP and head to "bfloat16 operands,
float32 accumulation": each rounds both operands to that dtype first and
still sums in float32 (``product``), in the forward pass and in the two
products of its gradient. The recurrence, the conv, norms and softmax stay
float32 whatever it says: the program's chunked form rounds the operands of
its own products, which the recurrence does not have.

**Planted faults** (keys of ``cfg``, for the limits' second readings):
``qk_l2norm`` False (q and k of the delta rule not normalised),
``decay`` False (alpha = 1), ``gate_then_norm`` True (Mamba's order),
``post_norm`` False (pre-norm blocks), ``qk_norm_per_head`` True (the full
layers' q/k norm over each head's d, the same gains); and
``linear_allow_neg_eigval`` False (beta not doubled).

Memory: 8192 tokens of a (30, 192, 96) state are 18 GB, so the recurrence is
a scan over segments of ``segment`` tokens, each recomputed in the backward
pass (``jax.checkpoint``): one segment's states are live at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


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


def attention(q, k, v, scale, block_q, operands=None):
    """q (Hq, T, d), k and v (Hkv, T, d) -> (Hq, T, d), causal. One block of
    queries at a time so that the (Hq, block, T) scores fit at T = 8192; the
    block is recomputed in the backward pass for the same reason."""
    hq, t, d = q.shape
    group = hq // k.shape[0]
    kk, vv = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    block_q = min(block_q, t)
    if t % block_q:
        raise ValueError(f"T={t} is not a multiple of the query block {block_q}")

    @jax.checkpoint
    def one_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block_q, block_q, axis=1)
        s = product("hqd,hkd->hqk", qb, kk, operands) * scale
        rows = i * block_q + jnp.arange(block_q)[:, None]
        seen = jnp.arange(t)[None, :] <= rows
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return product("hqk,hkd->hqd", p, vv, operands)

    out = jax.lax.map(one_block, jnp.arange(t // block_q))  # (nb, Hq, bq, d)
    return jnp.moveaxis(out, 0, 1).reshape(hq, t, d)


def causal_conv(u, w):
    """u (T, C), w (C, K): K shifted adds, no bias."""
    t, k = u.shape[0], w.shape[1]
    out = jnp.zeros_like(u)
    for i in range(k):
        lag = k - 1 - i
        shifted = jnp.concatenate([jnp.zeros_like(u[:lag]), u[:t - lag]])
        out = out + shifted * w[:, i]
    return out


def recurrence(q, k, v, alpha, beta, segment):
    """q and k (T, H, dk), v (T, H, dv), alpha and beta (T, H) -> o (T, H,
    dv): ``S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t
    k_t^T``, ``o_t = S_t q_t``, one token after another."""
    t, h, dk = q.shape
    segment = min(segment, t)
    if t % segment:
        raise ValueError(f"T={t} is not a multiple of the segment {segment}")

    def token(state, inputs):                  # state (H, dv, dk)
        q_t, k_t, v_t, a_t, b_t = inputs
        state = a_t[:, None, None] * state
        pred = jnp.einsum("hvd,hd->hv", state, k_t)
        state = state + jnp.einsum("hv,hd->hvd", b_t[:, None] * (v_t - pred),
                                   k_t)
        return state, jnp.einsum("hvd,hd->hv", state, q_t)

    @jax.checkpoint
    def one_segment(state, inputs):
        return jax.lax.scan(token, state, inputs)

    cut = lambda a: a.reshape((t // segment, segment) + a.shape[1:])  # noqa: E731
    _, o = jax.lax.scan(one_segment, jnp.zeros((h, v.shape[-1], dk), q.dtype),
                        tuple(map(cut, (q, k, v, alpha, beta))))
    return o.reshape(t, h, v.shape[-1])


def delta_mixer(x, lp, cfg, segment):
    """The Gated DeltaNet mixer over one record: x (T, D) -> (out (T, D),
    the mean of beta)."""
    t = x.shape[0]
    h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    project = functools.partial(product, "td,de->te",
                                operands=cfg.get("operands"))
    qkv = jax.nn.silu(causal_conv(jnp.concatenate(
        [project(x, lp[w]) for w in ("wq", "wk", "wv")], axis=-1),
        lp["conv_w"]))
    q, k, v = jnp.split(qkv, [h * dk, 2 * h * dk], axis=-1)
    q, k = q.reshape(t, h, dk), k.reshape(t, h, dk)
    if cfg.get("qk_l2norm", True):
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(project(x, lp["wb"]))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(lp["A_log"]) * jax.nn.softplus(
        project(x, lp["wa"]) + lp["dt_bias"]))
    if not cfg.get("decay", True):
        alpha = jnp.ones_like(alpha)
    o = recurrence(q * dk ** -0.5, k, v.reshape(t, h, dv), alpha, beta, segment)
    gate = jax.nn.silu(project(x, lp["wz"])).reshape(t, h, dv)
    eps = cfg["rms_norm_eps"]
    if cfg.get("gate_then_norm"):
        o = rms_norm(o * gate, lp["norm"], eps)
    else:
        o = rms_norm(o, lp["norm"], eps) * gate
    return project(o.reshape(t, h * dv), lp["wo"]), jnp.mean(beta)


def self_attention(x, lp, cfg, block_q):
    t = x.shape[0]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    project = functools.partial(product, "td,de->te",
                                operands=cfg.get("operands"))
    eps = cfg["rms_norm_eps"]

    def normed(y, gain, heads):
        if cfg.get("qk_norm_per_head"):
            return rms_norm(y.reshape(t, heads, d), gain.reshape(heads, d),
                            eps).reshape(t, heads * d)
        return rms_norm(y, gain, eps)

    q = normed(project(x, lp["wq"]), lp["q_norm"], hq)
    k = normed(project(x, lp["wk"]), lp["k_norm"], hkv)
    q = q.reshape(t, hq, d).transpose(1, 0, 2)
    k = k.reshape(t, hkv, d).transpose(1, 0, 2)
    v = project(x, lp["wv"]).reshape(t, hkv, d).transpose(1, 0, 2)
    a = attention(q, k, v, d ** -0.5, block_q, cfg.get("operands"))
    return project(a.transpose(1, 0, 2).reshape(t, hq * d), lp["wo"])


def mlp(x, lp, cfg):
    a, b = jnp.split(product("td,df->tf", x, lp["w_in"], cfg.get("operands")),
                     2, axis=-1)
    return product("tf,fd->td", jax.nn.silu(a) * b, lp["w_out"],
                   cfg.get("operands"))


def layer(x, lp, cfg, kind: str, block_q: int, segment: int):
    """One block over one record: x (T, D) -> (y (T, D), the mean of beta
    (0 for an attention layer))."""
    eps = cfg["rms_norm_eps"]
    post = cfg.get("post_norm", True)

    def branch(f, x, gain):
        return rms_norm(f(x), gain, eps) if post else f(rms_norm(x, gain, eps))

    beta = jnp.zeros((), jnp.float32)
    if kind == "linear_attention":
        def mixer(y):
            nonlocal beta
            out, beta = delta_mixer(y, lp, cfg, segment)
            return out
    else:
        def mixer(y):
            return self_attention(y, lp, cfg, block_q)
    x = x + branch(mixer, x, lp["ln1"])
    return x + branch(lambda y: mlp(y, lp, cfg), x, lp["ln2"]), beta


def forward(params, tokens, cfg, block_q: int = 512, segment: int = 64):
    """One record: tokens (T,) int -> (logits (T, V), the mean of beta over
    the linear-attention layers). Each layer is recomputed in the backward
    pass (``jax.checkpoint``)."""
    x = params["embed"][tokens]
    betas = []
    for kind, lp in zip(cfg["layer_types"], params["layers"]):
        x, beta = jax.checkpoint(lambda x, lp, kind=kind: layer(
            x, lp, cfg, kind, block_q, segment))(x, lp)
        if kind == "linear_attention":
            betas.append(beta)
    logits = product(
        "td,dv->tv", rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]),
        params["head"], cfg.get("operands"))
    return logits, jax.lax.stop_gradient(jnp.mean(jnp.stack(betas)))


def record_loss(params, tokens, labels, cfg, block_q: int = 512, at=None,
                segment: int = 64):
    """Summed cross-entropy of one record; beside it the mean of beta and,
    where ``at`` names positions, the logits there."""
    logits, beta = forward(params, tokens, cfg, block_q, segment)
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked), (beta, None if at is None else logits[at])


def loss_and_grad(params, tokens, labels, cfg, block_q: int = 512, at=None,
                  segment: int = 64):
    """Mean cross-entropy over a batch (N, T), its gradient, the mean of beta
    over the batch and the linear-attention layers, and the logits at the
    positions ``at`` (N, m) of each record (or None): record by record, so
    that one record's activations are live at a time."""
    n, t = tokens.shape
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, x, y, a: record_loss(p, x, y, cfg, block_q, a, segment),
        has_aux=True))
    total, grads, beta, logits = 0.0, None, 0.0, []
    for i in range(n):
        (l, (b, z)), g = grad_fn(params, tokens[i], labels[i],
                                 None if at is None else at[i])
        total, beta = total + l, beta + b
        logits.append(z)
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    scale = 1.0 / (n * t)
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    return (total * scale, grads, beta / n,
            None if at is None else jnp.stack(logits))


def delta_counters(beta_mean) -> dict:
    """The step's counter from ``loss_and_grad``'s mean of beta."""
    return {"delta_beta_mean": float(beta_mean)}
