"""The Gated DeltaNet mixer (Yang, Kautz & Hatamizadeh 2024,
arXiv:2412.06464): linear attention whose state is corrected by its own
prediction, its recurrence a chunked gated delta rule (``ops/delta_rule.py``).

``(N, T, D) -> (N, T, D)``, ``H`` heads of keys ``dk`` and of values ``dv``,
no bias anywhere::

    [q, k, v] = silu(conv([x Wq, x Wk, x Wv]))      depthwise, causal, kernel K
    q <- q / |q|,  k <- k / |k|                     per head (L2, eps 1e-6)
    beta = 2 sigmoid(x Wb)   (neg_eigval; else sigmoid(x Wb))   one a head and token
    log alpha = -exp(A_log) softplus(x Wa + dt_bias)            one a head and token
    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t / sqrt(dk)
    out = [RMSNorm_dv(o) * silu(x Wz)] Wo           the norm FIRST, then the
                                                    gate; one gain of dv shared
                                                    by the heads

With ``beta`` up to 2 (Grazzi et al., "Unlocking State-Tracking in Linear RNNs
Through Negative Eigenvalues", arXiv:2411.12537) the factor ``I - beta k
k^T`` may have an eigenvalue below 0.

Device time is attributed by ``jax.named_scope``: ``delta_proj`` (the six
in-projections, the gated norm and the out-projection), ``delta_conv`` (the
conv and its SiLU), ``delta_scan`` (the L2 norms, beta, the log decay and the
recurrence to o). The mean of beta over tokens and heads is the counter
``delta_beta_mean``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..utils import precision
from .module import AbstractModule
from .normalization import RMSNorm
from .ssm import causal_depthwise_conv


def _l2_normalized(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class GatedDeltaNet(AbstractModule):
    """Args: ``heads`` (key and value heads alike), ``key_dim`` and
    ``value_dim`` (their sizes), ``conv`` (the causal conv's kernel),
    ``neg_eigval`` (beta in (0, 2), else (0, 1)), ``eps`` (the output
    norm's).

    Initialisation (the published layer's): matrices N(0, ``init_std``); the
    conv's weight U(+-1/sqrt(K)); ``A_log = log U(0, 16)``; ``dt_bias =
    softplus^-1(exp U(log 0.001, log 0.1))``; the norm's gain 1.

    State: ``{"_counters": {"delta_beta_mean"}}``, see
    ``AbstractModule.counters_tree``."""

    def __init__(self, heads: int, key_dim: int, value_dim: int, conv: int = 4,
                 neg_eigval: bool = True, eps: float = 1e-6,
                 init_std: float = 0.02):
        super().__init__()
        self.heads, self.key_dim, self.value_dim = heads, key_dim, value_dim
        self.conv, self.neg_eigval = conv, neg_eigval
        self.init_std = init_std
        self._norm = RMSNorm(value_dim, eps)     # statistics in float32

    def infer_shape(self, in_spec):
        return jax.ShapeDtypeStruct(tuple(in_spec.shape), in_spec.dtype)

    def _build(self, rng, in_spec):
        d_model, h = in_spec.shape[-1], self.heads
        keys, values = h * self.key_dim, h * self.value_dim
        ks = jax.random.split(rng, 10)
        normal = lambda k, shape: self.init_std * jax.random.normal(  # noqa: E731
            k, shape, jnp.float32)
        dt = jnp.exp(jax.random.uniform(
            ks[8], (h,), jnp.float32, math.log(1e-3), math.log(0.1)))
        bound = 1.0 / math.sqrt(self.conv)
        params = {
            "wq": normal(ks[0], (d_model, keys)),
            "wk": normal(ks[1], (d_model, keys)),
            "wv": normal(ks[2], (d_model, values)),
            "wz": normal(ks[3], (d_model, values)),
            "wa": normal(ks[4], (d_model, h)),
            "wb": normal(ks[5], (d_model, h)),
            "conv_w": jax.random.uniform(ks[6], (2 * keys + values, self.conv),
                                         jnp.float32, -bound, bound),
            "A_log": jnp.log(jax.random.uniform(ks[7], (h,), jnp.float32,
                                                0.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),    # softplus^-1(dt)
            "norm": jnp.ones((self.value_dim,), jnp.float32),
            "wo": normal(ks[9], (values, d_model)),
        }
        return params, {"_counters": {
            "delta_beta_mean": jnp.zeros((), jnp.float32)}}

    def _apply(self, params, state, x, training, rng):
        from ..ops.delta_rule import delta_rule

        n, t, _ = x.shape
        h, dk, dv = self.heads, self.key_dim, self.value_dim
        with jax.named_scope("delta_proj"):
            qkv = jnp.concatenate(
                [precision.dot_acc32(x, params[w]) for w in ("wq", "wk", "wv")],
                axis=-1)
            z = precision.dot_acc32(x, params["wz"])
            a = precision.dot_acc32(x, params["wa"])
            b = precision.dot_acc32(x, params["wb"])
        with jax.named_scope("delta_conv"):
            qkv = jax.nn.silu(causal_depthwise_conv(qkv, params["conv_w"], 0.0))
        with jax.named_scope("delta_scan"):
            q, k, v = jnp.split(qkv, [h * dk, 2 * h * dk], axis=-1)
            q = _l2_normalized(q.reshape(n, t, h, dk)) * dk ** -0.5
            k = _l2_normalized(k.reshape(n, t, h, dk))
            beta = jax.nn.sigmoid(b)
            if self.neg_eigval:
                beta = 2.0 * beta
            log_alpha = -jnp.exp(params["A_log"]) * jax.nn.softplus(
                a + params["dt_bias"])
            o = delta_rule(q, k, v.reshape(n, t, h, dv), log_alpha, beta)
        with jax.named_scope("delta_proj"):
            o = self._norm._apply({"weight": params["norm"]}, {}, o, training,
                                  None)[0].reshape(n, t, h * dv)
            out = precision.dot_acc32(o * jax.nn.silu(z), params["wo"])
        return out.astype(x.dtype), {"_counters": {
            "delta_beta_mean": jax.lax.stop_gradient(jnp.mean(beta))}}
