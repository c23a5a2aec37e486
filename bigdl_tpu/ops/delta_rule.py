"""The gated delta rule (Yang, Kautz & Hatamizadeh, "Gated Delta Networks",
arXiv:2412.06464) in its chunked form: the recurrence of a linear-attention
layer whose state is corrected by its own prediction.

Per head, with a state S (dv, dk), a decay ``alpha_t = exp(log_alpha_t)`` in
(0, 1] and a write strength ``beta_t`` (in (0, 2) where negative eigenvalues
are allowed)::

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T      S_0 = 0
    o_t = S_t q_t

Run token by token that is T sequential steps. ``delta_rule`` cuts the record
into chunks of ``chunk`` tokens. With ``g`` the inclusive running sum of
``log_alpha`` INSIDE a chunk, ``gamma = exp(g)`` and ``Gamma[t, j] = exp(g_t
- g_j)`` for ``j <= t`` (every exponential of a difference of running sums
that is <= 0, never a ratio of exponentials), the state entering a chunk
``S`` and what each token writes ``delta_t = beta_t (v_t - alpha_t S_{t-1}
k_t)`` satisfy, over the chunk's rows::

    (I + A) Delta = diag(beta) V - diag(beta gamma) K S^T
    A = tril(diag(beta) (K K^T * Gamma), -1)                 strictly lower

so with ``T = (I + A)^-1`` (the WY/UT transform), ``U = T diag(beta) V`` and
``W = T diag(beta gamma) K``, ``Delta = U - W S^T``. Then::

    O      = diag(gamma) Q S^T + tril(Q K^T * Gamma) Delta
    S_next = gamma_last S + Delta^T (exp(g_last - g) * K)

Everything but the last line is parallel over the chunks: ``A``, the
triangular solve for ``U`` and ``W`` (forward substitution, float32: its
right-hand side is ``[beta V, beta gamma K]`` at once) and ``Q K^T * Gamma``.
The chunks' states run one after another in a ``lax.scan`` of two products a
chunk; the outputs are made after it from the states that entered each chunk.
The decays, beta, the solve and the carried state are float32; the matrix
products take their operands in ``Engine``'s compute dtype and sum in float32
(``utils/precision``), as the rest of the model's do.

The backward pass is autodiff through that form. What it keeps of one call is
of the chunks' size: the states entering the chunks (chunks x heads x dv x
dk) and the chunk-sized matrices; a model recomputes the layer under
``nn.Remat``, so one layer's are live at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..utils.precision import einsum_acc32
from .ssd import heads_within_budget


def _carry(decay, w, u, kd):
    """The chunks' states one after another from ``S = 0``: per chunk
    ``Delta = U - W S^T``, ``S <- decay S + Delta^T Kd``. ``decay`` (c, n, H),
    ``w`` and ``kd`` (c, n, H, C, dk), ``u`` (c, n, H, C, dv), the chunks
    leading -> (the states entering the chunks (c, n, H, dv, dk), Delta (c,
    n, H, C, dv))."""
    def step(state, inputs):
        by, w_c, u_c, kd_c = inputs
        delta = u_c - einsum_acc32("nhcd,nhvd->nhcv", w_c, state)
        return by[..., None, None] * state + einsum_acc32(
            "nhcv,nhcd->nhvd", delta, kd_c), (state, delta)

    n, h, dk, dv = w.shape[1], w.shape[2], w.shape[-1], u.shape[-1]
    _, (entering, delta) = jax.lax.scan(
        step, jnp.zeros((n, h, dv, dk), jnp.float32), (decay, w, u, kd))
    return entering, delta


def _chunked(q, k, v, log_alpha, beta, c: int):
    """The chunked form over whole chunks: q and k (n, T, H, dk), v (n, T,
    H, dv), log_alpha and beta (n, T, H) float32, T a multiple of ``c`` -> o
    (n, T, H, dv) float32."""
    n, t, h, dk = k.shape
    dv = v.shape[-1]
    nc = t // c

    def chunked(a):               # (n, T, H, ...) -> (n, nc, H, C, ...)
        a = a.reshape((n, nc, c, h) + a.shape[3:])
        return jnp.moveaxis(a, 3, 2)

    q, k, v = chunked(q), chunked(k), chunked(v)
    g = jnp.cumsum(chunked(log_alpha), axis=-1)              # (n, nc, H, C)
    beta = chunked(beta)
    seen = jnp.tril(jnp.ones((c, c), bool))
    # Gamma[t, j] = exp(g_t - g_j) on and below the diagonal, 0 above
    gamma_tj = jnp.exp(jnp.where(seen, g[..., :, None] - g[..., None, :],
                                 -jnp.inf))
    a = jnp.tril(beta[..., None] * einsum_acc32("nxhcd,nxhjd->nxhcj", k, k)
                 * gamma_tj, -1)
    rhs = beta[..., None] * jnp.concatenate(
        [v.astype(jnp.float32), jnp.exp(g)[..., None] * k.astype(jnp.float32)],
        axis=-1)
    uw = jax.lax.linalg.triangular_solve(a, rhs, left_side=True, lower=True,
                                         unit_diagonal=True)
    u, w = uw[..., :dv], uw[..., dv:]
    last = g[..., -1:]
    kd = jnp.exp(last - g)[..., None] * k.astype(jnp.float32)
    lead = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    entering, delta = _carry(lead(jnp.exp(last[..., 0])), lead(w), lead(u),
                             lead(kd))
    entering, delta = jnp.moveaxis(entering, 0, 1), jnp.moveaxis(delta, 0, 1)
    scores = einsum_acc32("nxhcd,nxhjd->nxhcj", q, k) * gamma_tj
    o = jnp.exp(g)[..., None] * einsum_acc32("nxhcd,nxhvd->nxhcv", q, entering) \
        + einsum_acc32("nxhcj,nxhjv->nxhcv", scores, delta)
    return jnp.moveaxis(o, 2, 3).reshape(n, t, h, dv)


def head_group(records: int, tokens: int, heads: int, key_dim: int,
               value_dim: int) -> int:
    """Heads whose chunked form is live at once: those whose float32 [K, V]
    over the record fit ``ops/ssd``'s budget (``heads_within_budget``)."""
    return heads_within_budget(heads,
                               records * tokens * (key_dim + value_dim) * 4)


def delta_rule(q, k, v, log_alpha, beta, chunk: int = 64):
    """q and k (n, T, H, dk), v (n, T, H, dv), log_alpha and beta (n, T, H)
    -> o (n, T, H, dv) float32. q and k come as the recurrence reads them
    (normalised and scaled by the caller); ``log_alpha`` <= 0. A record
    shorter than one chunk is one chunk, and a ragged last chunk is padded
    with tokens that leave the state as it is (beta = 0, log_alpha = 0). The
    heads run in groups (``head_group``, from the shapes), each recomputed
    in the backward pass (``jax.checkpoint``), so that what is live is one
    group's chunk-sized arrays and what the backward keeps is the groups'
    inputs."""
    n, t, h, dk = k.shape
    dv = v.shape[-1]
    c = min(chunk, t)
    pad = -t % c
    log_alpha = log_alpha.astype(jnp.float32)
    beta = beta.astype(jnp.float32)
    if pad:
        widen = lambda a: jnp.pad(  # noqa: E731
            a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        q, k, v, log_alpha, beta = map(widen, (q, k, v, log_alpha, beta))
    group = head_group(n, t + pad, h, dk, dv)
    if group == h:
        return _chunked(q, k, v, log_alpha, beta, c)[:, :t]

    def by_group(a):              # (n, T, H, ...) -> (H / g, n, T, g, ...)
        a = a.reshape(a.shape[:2] + (h // group, group) + a.shape[3:])
        return jnp.moveaxis(a, 2, 0)

    o = jax.lax.map(jax.checkpoint(lambda args: _chunked(*args, c)),
                    tuple(map(by_group, (q, k, v, log_alpha, beta))))
    return jnp.moveaxis(o, 0, 2).reshape(n, t + pad, h, dv)[:, :t]

