"""Plain float32 reference of the state-space / attention / routed-experts
hybrid whose every layer is ONE mixer, which ``bigdl_tpu.models.decoder_lm``
builds from ``nemotron_h``'s key set (NVIDIA-Nemotron-3-Nano-30B-A3B is the
first model on it): forward pass, loss, ``jax.grad``, the router's bias update
and the counters in straightforward ``jax.numpy``, record by record. No
kernel, no chunked form, no sort, no cache, no batching: the state-space layer
is **the recurrence as written**, one token after another, with its B/C
groups; attention is a masked softmax by blocks of queries; the experts are a
loop over the experts held with a mask.

The equations (sizes from the config dict; ``RMSNorm(x; g) = x * rsqrt(mean(x^2)
+ eps) * g``, eps ``layer_norm_epsilon``)::

    h_0 = E[token]                                             no multiplier
    layer i of kind k:   h_{i+1} = h_i + mixer_k(RMSNorm(h_i; g_i))
    logits = RMSNorm(h_L; g_f) W_head                           untied

    mamba (heads H of P, state N, G = n_groups B/C groups, conv K):
      [z | xBC | dt] = u W_in       widths H P | H P + 2 G N | H, no bias
      xBC = silu(conv(xBC) + b)     depthwise and causal: conv(v)[t] =
                                    sum_k w[:, k] v[t - (K-1) + k], v zero
                                    before the record
      [x | B | C] = split(xBC)      x (T, H, P); B, C (T, G, N)
      dt = softplus(dt + dt_bias);  A = -exp(A_log)             one scalar a head
      head h, group g = h // (H / G):
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[g]   (S_0 = 0)
        y_t = S_t C_t[g] + D_h x_t
      y = y * silu(z)                                           gate first
      y = y / sqrt(mean over EACH GROUP's H P / G channels of y^2 + eps) * g_norm
      out = y W_out

    attention: q = u W_q (Hq heads of d), k = u W_k, v = u W_v (Hkv heads), no
      bias, NO positional encoding, no norm on q or k; query head j reads K/V
      head j // (Hq / Hkv); causal softmax(q k^T / sqrt(d)) v; out = o W_o

    experts: s = sigmoid(u W_r) over all E;  chosen = top-k of (s + b);
      w = s[chosen] / (sum + 1e-20) * routed_scaling_factor
      routed = sum over chosen AND HELD e of w_e W_down,e relu(W_up,e u)^2
      out = routed + W_down,s relu(W_up,s u)^2                  the shared expert
      pairs routed to experts that are not held add nothing: the chip's share
      of an expert-parallel layer, without its exchange
      b (E,) is state: no gradient; after a training step b_e += rate *
      sign(mean_e' c_e' - c_e), c_e the step's (token, choice) pairs that
      chose expert e, over all E experts

    loss: mean over positions of CE(logits[t], labels[t]) (the caller shifts)

**Departures from the published model, each assumed** (its config names the
mechanisms and not these): the bias update's speed (DeepSeek-V3's 0.001,
arXiv:2412.19437); no auxiliary balance loss beside the bias; no positional
encoding in attention (``rope_theta`` in the config is unused by
``nemotron_h``'s attention); packed records with no document mask, so the
state and the attention cross document boundaries; the router's
``n_group`` / ``topk_group`` are 1 (no group-limited routing); the pattern's
``-`` (a dense relu2 MLP layer) does not occur and is not written here.

Parameters, one float32 array each::

    {"embed": (V, D), "final_norm": (D,), "head": (D, V),
     "layers": [mamba: {"ln": (D,), "in_proj": (D, 2 H P + 2 G N + H),
                        "conv_w": (H P + 2 G N, K), "conv_b": (H P + 2 G N,),
                        "A_log": (H,), "dt_bias": (H,), "D": (H,),
                        "norm": (H P,), "out_proj": (H P, D)}
                attention: {"ln", "wq": (D, Hq d), "wk": (D, Hkv d),
                            "wv": (D, Hkv d), "wo": (Hq d, D)}
                experts: {"ln", "router": (D, E), "w_up": (E_held, D, F),
                          "w_down": (E_held, F, D), "shared_in": (D, Fs),
                          "shared_out": (Fs, D)}, ...]}

``biases`` is a list of (E,) arrays, one for each experts layer in order.

Callers on a TPU wrap calls in ``jax.default_matmul_precision("highest")``.
``cfg["operands"]`` (a dtype name) rounds both operands of every matrix
product but the router's to that dtype and still sums in float32, forward and
backward: the reading "bfloat16 operands, float32 accumulation". The
recurrence, the conv, norms, the router and softmax stay float32 whatever it
says: the program's chunked form rounds the operands of its own four
products, which the recurrence does not have.

Memory: 8192 tokens of a (64, 64, 128) state are 17 GB, so the recurrence is
a scan over segments of ``chunk_size`` tokens, each recomputed in the backward
pass (``jax.checkpoint``). The segment ends are also where the program's
chunks end, so ``ssm_state_rms`` reads the reference's own states there.

Planted faults for the comparison's second readings, never the model
(``cfg`` keys): ``scan_group_zero`` (every head reads B/C group 0),
``norm_over_all`` (the gated norm's statistic over all of H P),
``gated_expert`` (the experts' hidden is ``silu(a) * a`` in place of
``relu(a)^2``), ``bias_in_weights`` (``b`` added to the chosen scores).
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


# ------------------------------------------------------------------- mamba

def causal_conv(u, w, b):
    """u (T, C), w (C, K), b (C,): K shifted adds."""
    t, k = u.shape[0], w.shape[1]
    out = jnp.broadcast_to(b, u.shape)
    for i in range(k):
        lag = k - 1 - i
        shifted = jnp.concatenate([jnp.zeros_like(u[:lag]), u[:t - lag]])
        out = out + shifted * w[:, i]
    return out


def recurrence(x, dt, a, b, c, d, segment):
    """x (T, H, P), dt (T, H), a (H,), b and c (T, G, N), d (H,) -> (y (T, H,
    P), the states at the segments' ends (T / segment, H, P, N)): head h
    reads group ``h // (H / G)``; one token after another."""
    t, h, p = x.shape
    per_group = h // b.shape[1]
    segment = min(segment, t)
    if t % segment:
        raise ValueError(f"T={t} is not a multiple of the segment {segment}")

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs            # (H, P), (H,), (G, N), (G, N)
        b_h = jnp.repeat(b_t, per_group, axis=0)                   # (H, N)
        c_h = jnp.repeat(c_t, per_group, axis=0)
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_h) + d[:, None] * x_t

    @jax.checkpoint
    def one_segment(state, inputs):
        state, y = jax.lax.scan(token, state, inputs)
        return state, (y, state)

    cut = lambda v: v.reshape((t // segment, segment) + v.shape[1:])  # noqa: E731
    _, (y, ends) = jax.lax.scan(
        one_segment, jnp.zeros((h, p, b.shape[-1]), x.dtype),
        (cut(x), cut(dt), cut(b), cut(c)))
    return y.reshape(t, h, p), ends


def mamba(u, lp, cfg):
    """The mixer over one record: u (T, D), already normed -> (out (T, D),
    (the least running log decay inside a segment, the summed squares of the
    states at the segments' ends))."""
    t = u.shape[0]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, g = cfg["ssm_state_size"], cfg["n_groups"]
    z, xbc, dt = jnp.split(
        product("td,de->te", u, lp["in_proj"], cfg.get("operands")),
        [h * p, 2 * h * p + 2 * g * n], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"]))
    xs, b, c = jnp.split(xbc, [h * p, h * p + g * n], axis=-1)
    b, c = b.reshape(t, g, n), c.reshape(t, g, n)
    if cfg.get("scan_group_zero"):     # a planted fault, never the model
        b, c = (jnp.broadcast_to(v[:, :1], v.shape) for v in (b, c))
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    a = -jnp.exp(lp["A_log"])
    segment = min(cfg["chunk_size"], t)
    y, ends = recurrence(xs.reshape(t, h, p), dt, a, b, c, lp["D"], segment)
    y = y.reshape(t, h * p) * jax.nn.silu(z)
    groups = 1 if cfg.get("norm_over_all") else g       # (planted fault)
    y = rms_norm(y.reshape(t, groups, -1), 1.0,
                 cfg["layer_norm_epsilon"]).reshape(t, h * p) * lp["norm"]
    log_decay = jnp.cumsum((dt * a).reshape(t // segment, segment, h), axis=1)
    stats = jax.lax.stop_gradient(
        (jnp.min(log_decay).astype(jnp.float32),
         jnp.sum(jnp.square(ends.astype(jnp.float32)))))
    return product("te,ed->td", y, lp["out_proj"], cfg.get("operands")), stats


# --------------------------------------------------------------- attention

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


def self_attention(u, lp, cfg, block_q):
    t = u.shape[0]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    project = functools.partial(product, "td,de->te",
                                operands=cfg.get("operands"))
    q = project(u, lp["wq"]).reshape(t, hq, d).transpose(1, 0, 2)
    k = project(u, lp["wk"]).reshape(t, hkv, d).transpose(1, 0, 2)
    v = project(u, lp["wv"]).reshape(t, hkv, d).transpose(1, 0, 2)
    a = attention(q, k, v, 1.0 / math.sqrt(d), block_q, cfg.get("operands"))
    return project(a.transpose(1, 0, 2).reshape(t, hq * d), lp["wo"])


# ----------------------------------------------------------------- experts

def route(u, router, bias, cfg):
    """u (T, D) -> (weights (T, k), expert ids (T, k))."""
    s = jax.nn.sigmoid(u @ router)
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(bias),
                             cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    if cfg.get("bias_in_weights"):     # a planted fault, never the model
        top_s = top_s + bias[top_e]
    w = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"], top_e


def relu2_mlp(u, w_up, w_down, cfg):
    a = product("td,df->tf", u, w_up, cfg.get("operands"))
    hidden = jax.nn.silu(a) * a if cfg.get("gated_expert") \
        else jnp.square(jax.nn.relu(a))                 # (planted fault)
    return product("tf,fd->td", hidden, w_down, cfg.get("operands"))


def experts(u, lp, bias, cfg):
    """u (T, D) -> (this share's part of the routed sum plus the shared
    expert (T, D), pairs that chose each expert of the router (E,)). A loop
    over the experts held, each over ALL tokens with a mask; ``lax.scan``
    only so that one expert's body is compiled, not eight."""
    top_w, top_e = route(u, lp["router"], bias, cfg)

    def one_expert(out, expert):
        e, w_up, w_down = expert
        w = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)    # (T,)
        return out + w[:, None] * relu2_mlp(u, w_up, w_down, cfg), None

    held = jnp.asarray(cfg["experts_held"], top_e.dtype)
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                          (held, lp["w_up"], lp["w_down"]))
    out = out + relu2_mlp(u, lp["shared_in"], lp["shared_out"], cfg)
    counts = jnp.sum(jax.nn.one_hot(top_e.reshape(-1), lp["router"].shape[1],
                                    dtype=jnp.float32), axis=0)
    return out, counts


# ------------------------------------------------------------------- model

def layer(x, lp, bias, cfg, kind: str, block_q: int):
    """One layer over one record: x (T, D) -> (y (T, D), the mixer's
    statistics: a mamba layer's pair, an experts layer's counts, else None)."""
    u = rms_norm(x, lp["ln"], cfg["layer_norm_epsilon"])
    if kind == "mamba":
        mixed, stats = mamba(u, lp, cfg)
    elif kind == "experts":
        mixed, stats = experts(u, lp, bias, cfg)
    else:
        mixed, stats = self_attention(u, lp, cfg, block_q), None
    return x + mixed, stats


def forward(params, biases, tokens, cfg, block_q: int = 512):
    """One record: tokens (T,) int -> (logits (T, V), (the most negative
    running log decay inside a segment over all mamba layers, the summed
    squares of the last mamba layer's states at the segments' ends), pairs
    per experts layer and expert (L_experts, E)). Each layer is recomputed in
    the backward pass (``jax.checkpoint``)."""
    biases = list(biases)
    x = params["embed"][tokens]
    low, squares = jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)
    counts = []
    for kind, lp in zip(cfg["layer_types"], params["layers"]):
        bias = biases.pop(0) if kind == "experts" else None
        x, stats = jax.checkpoint(
            lambda x, lp, bias, kind=kind: layer(x, lp, bias, cfg, kind,
                                                 block_q))(x, lp, bias)
        if kind == "mamba":
            low, squares = jnp.minimum(low, stats[0]), stats[1]
        elif kind == "experts":
            counts.append(stats)
    logits = product(
        "td,dv->tv", rms_norm(x, params["final_norm"],
                              cfg["layer_norm_epsilon"]),
        params["head"], cfg.get("operands"))
    return logits, (low, squares), jnp.stack(counts)


def record_loss(params, biases, tokens, labels, cfg, block_q: int = 512,
                at=None):
    """Summed cross-entropy of one record; beside it the scan's statistics,
    the routing counts and, where ``at`` names positions, the logits there."""
    logits, stats, counts = forward(params, biases, tokens, cfg, block_q)
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked), (stats, counts,
                                   None if at is None else logits[at])


def bias_update(bias, counts, rate: float):
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


def loss_and_grad(params, biases, tokens, labels, cfg, block_q: int = 512,
                  at=None):
    """Mean cross-entropy over a batch (N, T), its gradient, ``stats``
    (``counts`` (L_experts, E) summed over the batch, ``biases``: the biases
    after the step, ``log_decay_min``, ``state_squares``) and the logits at
    the positions ``at`` (N, m) of each record (or None): record by record,
    so that one record's activations are live at a time."""
    n, t = tokens.shape
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, x, y, a: record_loss(p, biases, x, y, cfg, block_q, a),
        has_aux=True))
    total, grads, low, squares, counts, logits = 0.0, None, 0.0, 0.0, 0, []
    for i in range(n):
        (l, ((lo, sq), c, z)), g = grad_fn(params, tokens[i], labels[i],
                                           None if at is None else at[i])
        total, low, squares = total + l, jnp.minimum(low, lo), squares + sq
        counts = counts + c
        logits.append(z)
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    scale = 1.0 / (n * t)
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    rate = float(cfg["bias_update_rate"])
    stats = {"counts": counts, "log_decay_min": low, "state_squares": squares,
             "biases": [bias_update(b.astype(jnp.float32), c, rate)
                        for b, c in zip(biases, counts.astype(jnp.float32))]}
    return (total * scale, grads, stats,
            None if at is None else jnp.stack(logits))


def counters(stats, cfg, records: int, tokens: int):
    """The step's counters from ``loss_and_grad``'s ``stats`` over ``records``
    records of ``tokens``: the scan's two (``ssm_log_decay_min``, the root
    mean square of the last mamba layer's states at the segments' ends), the
    pairs that hit a held expert (summed over the experts layers), the worst
    layer's load max over mean among the experts held, dropped pairs (none:
    nothing is ever dropped), the largest |b| after the step."""
    ends = -(-tokens // min(cfg["chunk_size"], tokens))
    count = records * ends * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"]
    held = jnp.asarray(cfg["experts_held"])
    counts = jnp.asarray(stats["counts"], jnp.float32)[:, held]
    load = jnp.max(counts, axis=-1) / jnp.maximum(jnp.mean(counts, axis=-1), 1.0)
    return {"ssm_log_decay_min": float(stats["log_decay_min"]),
            "ssm_state_rms": float(jnp.sqrt(stats["state_squares"] / count)),
            "moe_pairs_local": float(jnp.sum(counts)),
            "moe_load_max_over_mean": float(jnp.max(load)),
            "moe_dropped_pairs": 0.0,
            "moe_bias_abs_max": float(max(jnp.max(jnp.abs(b))
                                          for b in stats["biases"]))}
