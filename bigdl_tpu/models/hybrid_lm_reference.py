"""Plain float32 reference of the state-space / attention hybrid language
model that ``bigdl_tpu.models.decoder_lm`` builds from a
``granitemoehybrid``-style config: forward pass, loss and ``jax.grad`` in
straightforward ``jax.numpy``, record by record. No kernel, no chunked form,
no cache, no batching: the state-space layer is **the recurrence as written**,
one token after another; attention is dense, by blocks of queries.

The equations (sizes from the catalog-style config dict, see ``decoder_lm``):

* ``x_0 = embedding_multiplier * E[token]``; block: ``h = x + r
  Mixer(RMSNorm(x))``, ``y = h + r MLP(RMSNorm(h))`` with ``r`` the
  ``residual_multiplier``; ``logits = RMSNorm(x_L) E^T / logits_scaling``: the
  head is the embedding's transpose, one leaf whose gradient is the sum of
  both uses. ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * g``. No bias except
  the conv's.
* MLP: ``[a, b] = split(x W_in)``, ``(silu(a) * b) W_out``.
* attention (``layer_types`` ``attention``): ``q = x W_q``, ``k = x W_k``,
  ``v = x W_v``, no norm on q or k, **no positional encoding**; query head
  ``h`` reads K/V head ``h // (Hq / Hkv)``; scores ``q k^T *
  attention_multiplier``; query ``i`` sees key ``j`` iff ``j <= i``.
* Mamba-2 mixer (``layer_types`` ``mamba``), ``d_inner = H P``:
  ``[z, xBC, dt] = split(x in_proj)``, widths ``d_inner``, ``d_inner + 2 N``,
  ``H``; ``xBC = silu(conv(xBC))``, depthwise and causal with a bias:
  ``conv(u)[t] = b + sum_k w[:, k] u[t - (K-1) + k]``, ``u`` zero before the
  record; ``[x, B, C] = split(xBC)``, widths ``d_inner``, ``N``, ``N`` (B
  and C shared by all heads); ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``, one scalar a head; per head ``S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t (x) B_t`` (``S_0 = 0``), ``y_t = S_t C_t + D x_t``; ``out =
  RMSNorm(y * silu(z)) out_proj`` over all of ``d_inner`` (gate first, then
  the norm, one group, learned gain).
* loss: mean over positions of the cross-entropy of ``logits[t]`` against
  ``labels[t]`` (the caller shifts: the label of position t is token t + 1).

**Assumed** (the config of the first model built on this,
granite-4.0-h-micro, has no key for them): packed records with no document
mask, so the state and the attention cross document boundaries; no auxiliary
loss.

Parameters, one float32 array each::

    {"embed": (V, D), "final_norm": (D,),
     "layers": [mamba: {"ln1": (D,), "in_proj": (D, 2 HP + 2 N + H),
                        "conv_w": (HP + 2 N, K), "conv_b": (HP + 2 N,),
                        "A_log": (H,), "dt_bias": (H,), "D": (H,),
                        "norm": (HP,), "out_proj": (HP, D),
                        "ln2": (D,), "w_in": (D, 2 F), "w_out": (F, D)}
                attention: {"ln1", "wq": (D, Hq d), "wk": (D, Hkv d),
                            "wv": (D, Hkv d), "wo": (Hq d, D),
                            "ln2", "w_in", "w_out"}, ...]}

Callers on a TPU wrap calls in ``jax.default_matmul_precision("highest")``:
a float32 matrix product otherwise runs in one bf16 pass there.

**At a stated precision.** ``cfg["operands"] = "bfloat16"`` holds the matrix
products of the projections, attention, MLP and head to "bfloat16 operands,
float32 accumulation": each rounds both operands to that dtype first and
still sums in float32 (``product``), in the forward pass and in the two
products of its gradient. The recurrence, the conv, norms and softmax stay
float32 whatever it says: the program's chunked form rounds the operands of
its own four products, which the recurrence does not have.

Memory: 8192 tokens of a (64, 64, 128) state are 17 GB, so the recurrence is
a scan over segments of ``mamba_chunk_size`` tokens, each recomputed in the
backward pass (``jax.checkpoint``): one segment's states are live at a time.
The segment ends are also where the program's chunks end, so the two counters
(``scan_counters``) read the reference's own states there.
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


def causal_conv(u, w, b):
    """u (T, C), w (C, K), b (C,): K shifted adds."""
    t, k = u.shape[0], w.shape[1]
    out = jnp.broadcast_to(b, u.shape)
    for i in range(k):
        lag = k - 1 - i
        shifted = jnp.concatenate([jnp.zeros_like(u[:lag]), u[:t - lag]])
        out = out + shifted * w[:, i]
    return out


def recurrence(x, dt, a, b, c, d, segment, carried=True):
    """x (T, H, P), dt (T, H), a (H,), b and c (T, N), d (H,) -> (y (T, H, P),
    the states at the segments' ends (T / segment, H, P, N)): ``S_t =
    exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``, one
    token after another. ``carried=False`` is a planted fault for the
    limits' second readings (``cfg["state_carried"]``): every segment starts
    from the zero state, as a chunked form that lost its carry would."""
    t, h, p = x.shape
    segment = min(segment, t)
    if t % segment:
        raise ValueError(f"T={t} is not a multiple of the segment {segment}")

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs               # (H, P), (H,), (N,), (N,)
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t
        return state, jnp.einsum("hpn,n->hp", state, c_t) + d[:, None] * x_t

    @jax.checkpoint
    def one_segment(state, inputs):
        state, y = jax.lax.scan(
            token, state if carried else jnp.zeros_like(state), inputs)
        return state, (y, state)

    cut = lambda v: v.reshape((t // segment, segment) + v.shape[1:])  # noqa: E731
    _, (y, ends) = jax.lax.scan(
        one_segment, jnp.zeros((h, p, b.shape[-1]), x.dtype),
        (cut(x), cut(dt), cut(b), cut(c)))
    return y.reshape(t, h, p), ends


def mamba(x, lp, cfg):
    """The mixer over one record: x (T, D) -> (out (T, D), statistics)."""
    t = x.shape[0]
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    z, xbc, dt = jnp.split(
        product("td,de->te", x, lp["in_proj"], cfg.get("operands")),
        [h * p, 2 * h * p + 2 * n], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"]))
    xs, b, c = jnp.split(xbc, [h * p, h * p + n], axis=-1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    a = -jnp.exp(lp["A_log"])
    segment = min(cfg["mamba_chunk_size"], t)
    y, ends = recurrence(xs.reshape(t, h, p), dt, a, b, c, lp["D"], segment,
                         cfg.get("state_carried", True))
    y = rms_norm(y.reshape(t, h * p) * jax.nn.silu(z), lp["norm"],
                 cfg["rms_norm_eps"])
    log_decay = jnp.cumsum((dt * a).reshape(t // segment, segment, h), axis=1)
    stats = jax.lax.stop_gradient(
        (jnp.min(log_decay).astype(jnp.float32),
         jnp.sum(jnp.square(ends.astype(jnp.float32)))))
    return product("te,ed->td", y, lp["out_proj"], cfg.get("operands")), stats


def self_attention(x, lp, cfg, block_q):
    t = x.shape[0]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    project = functools.partial(product, "td,de->te",
                                operands=cfg.get("operands"))
    q = project(x, lp["wq"]).reshape(t, hq, d).transpose(1, 0, 2)
    k = project(x, lp["wk"]).reshape(t, hkv, d).transpose(1, 0, 2)
    v = project(x, lp["wv"]).reshape(t, hkv, d).transpose(1, 0, 2)
    a = attention(q, k, v, cfg["attention_multiplier"], block_q,
                  cfg.get("operands"))
    return project(a.transpose(1, 0, 2).reshape(t, hq * d), lp["wo"])


def mlp(x, lp, cfg):
    a, b = jnp.split(product("td,df->tf", x, lp["w_in"], cfg.get("operands")),
                     2, axis=-1)
    return product("tf,fd->td", jax.nn.silu(a) * b, lp["w_out"],
                   cfg.get("operands"))


def layer(x, lp, cfg, kind: str, block_q: int):
    """One block over one record: x (T, D) -> (y (T, D), the mixer's
    statistics (zeros for an attention layer))."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    y = rms_norm(x, lp["ln1"], eps)
    if kind == "mamba":
        mixed, stats = mamba(y, lp, cfg)
    else:
        mixed = self_attention(y, lp, cfg, block_q)
        stats = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    x = x + r * mixed
    return x + r * mlp(rms_norm(x, lp["ln2"], eps), lp, cfg), stats


def forward(params, tokens, cfg, block_q: int = 512):
    """One record: tokens (T,) int -> (logits (T, V), (the most negative
    running log decay inside a segment over all mamba layers, the sum of
    squares of the last mamba layer's states at the segments' ends)). Each
    layer is recomputed in the backward pass (``jax.checkpoint``)."""
    x = params["embed"][tokens] * cfg["embedding_multiplier"]
    low, squares = jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)
    for kind, lp in zip(cfg["layer_types"], params["layers"]):
        x, (lo, sq) = jax.checkpoint(
            lambda x, lp, kind=kind: layer(x, lp, cfg, kind, block_q))(x, lp)
        if kind == "mamba":
            low, squares = jnp.minimum(low, lo), sq
    logits = product(
        "td,vd->tv", rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]),
        params["embed"], cfg.get("operands")) / cfg["logits_scaling"]
    return logits, (low, squares)


def record_loss(params, tokens, labels, cfg, block_q: int = 512, at=None):
    """Summed cross-entropy of one record; beside it the statistics and,
    where ``at`` names positions, the logits there."""
    logits, stats = forward(params, tokens, cfg, block_q)
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked), (stats, None if at is None else logits[at])


def loss_and_grad(params, tokens, labels, cfg, block_q: int = 512, at=None):
    """Mean cross-entropy over a batch (N, T), its gradient, the statistics
    over the batch (the least log decay, the summed squares) and the logits
    at the positions ``at`` (N, m) of each record (or None): record by
    record, so that one record's activations are live at a time."""
    n, t = tokens.shape
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, x, y, a: record_loss(p, x, y, cfg, block_q, a), has_aux=True))
    total, grads, low, squares, logits = 0.0, None, 0.0, 0.0, []
    for i in range(n):
        (l, ((lo, sq), z)), g = grad_fn(params, tokens[i], labels[i],
                                        None if at is None else at[i])
        total, low, squares = total + l, jnp.minimum(low, lo), squares + sq
        logits.append(z)
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    scale = 1.0 / (n * t)
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    return (total * scale, grads, (low, squares),
            None if at is None else jnp.stack(logits))


def scan_counters(stats, cfg, records: int, tokens: int):
    """The step's two counters from ``loss_and_grad``'s statistics over
    ``records`` records of ``tokens``: ``ssm_log_decay_min`` and the root
    mean square of the last mamba layer's states at the segments' ends."""
    low, squares = stats
    ends = -(-tokens // min(cfg["mamba_chunk_size"], tokens))
    count = records * ends * cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
        * cfg["mamba_d_state"]
    return {"ssm_log_decay_min": float(low),
            "ssm_state_rms": float(jnp.sqrt(squares / count))}
