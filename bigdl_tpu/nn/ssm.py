"""The Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060): a state-space layer
whose recurrence runs as a chunked scan (``ops/ssd.py``).

``(N, T, D) -> (N, T, D)`` with ``d_inner = heads * head_dim``, ``G =
groups`` B/C groups of ``state`` (one shared by all heads, or several: head
``h`` then reads group ``h // (heads / G)``), no bias except the conv's::

    [z, xBC, dt] = split(h in_proj)         widths d_inner, d_inner + 2 G state, heads
    xBC = silu(conv1d(xBC))                 depthwise, causal, kernel k, with bias
    [x, B, C] = split(xBC)                  widths d_inner, G state, G state
    dt = softplus(dt + dt_bias),  A = -exp(A_log)           one scalar a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[g],  y_t = S_t C_t[g] + D x_t
    out = RMSNorm(y * silu(z)) out_proj     gate first, then the norm: its
                                            statistic over each group's
                                            d_inner / G channels

Device time is attributed by ``jax.named_scope``: ``ssm_proj`` (both
projections and the gated norm), inside it ``ssm_gate_norm`` (the gate and the
norm alone: elementwise passes, no product), ``ssm_conv``, ``ssm_scan`` (from
dt to y, the D term included).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..utils import precision
from .module import AbstractModule
from .normalization import RMSNorm


def causal_depthwise_conv(x, weight, bias):
    """x (N, T, C), weight (C, K), bias (C,): ``out[t] = bias + sum_k
    weight[:, k] x[t - (K - 1) + k]``, tokens before the record's first
    being zero: K shifted adds (K is 4; a grouped convolution of one channel
    a group is what the TPU's convolution unit does worst)."""
    k = weight.shape[1]
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = bias
    for i in range(k):
        out = out + padded[:, i:i + t] * weight[:, i]
    return out


class Mamba2Mixer(AbstractModule):
    """Args: ``heads`` x ``head_dim`` = d_inner; ``state``: N of a B/C
    group; ``groups``: how many B/C groups (a divisor of ``heads``; also the
    groups of the gated norm's statistic); ``conv``: the causal conv's
    kernel; ``chunk``: the scan's chunk (``mamba_chunk_size``);
    ``report_state``: also count ``ssm_state_rms`` (the model asks its last
    such layer).

    Initialisation (the ``mamba_ssm`` Mamba-2 defaults): matrices
    N(0, ``init_std``); the conv's weight and bias U(+-1/sqrt(K));
    ``A_log = log U(1, 16)``; ``dt_bias = softplus^-1(exp U(log 0.001,
    log 0.1))``; ``D`` and the norm's gain 1.

    State: ``{"_counters": {ssm_log_decay_min[, ssm_state_rms]}}``, see
    ``AbstractModule.counters_tree``."""

    def __init__(self, heads: int, head_dim: int, state: int, conv: int = 4,
                 chunk: int = 256, eps: float = 1e-5, init_std: float = 0.02,
                 report_state: bool = False, groups: int = 1):
        super().__init__()
        if heads % groups:
            raise ValueError(f"{heads} heads do not split into {groups} "
                             "B/C groups")
        self.heads, self.head_dim, self.state = heads, head_dim, state
        self.conv, self.chunk, self.groups = conv, chunk, groups
        self.init_std, self.report_state = init_std, report_state
        self._norm = RMSNorm(heads * head_dim, eps)  # statistics in float32

    def infer_shape(self, in_spec):
        return jax.ShapeDtypeStruct(tuple(in_spec.shape), in_spec.dtype)

    def _counters(self, *values):
        names = ("ssm_log_decay_min", "ssm_state_rms")
        return {"_counters": dict(zip(names[:1 + self.report_state], values))}

    def _build(self, rng, in_spec):
        d_model = in_spec.shape[-1]
        d_inner, channels = self.heads * self.head_dim, \
            self.heads * self.head_dim + 2 * self.groups * self.state
        ks = jax.random.split(rng, 6)
        normal = lambda k, shape: self.init_std * jax.random.normal(  # noqa: E731
            k, shape, jnp.float32)
        dt = jnp.exp(jax.random.uniform(
            ks[3], (self.heads,), jnp.float32, math.log(1e-3), math.log(0.1)))
        bound = 1.0 / math.sqrt(self.conv)
        params = {
            "in_proj": normal(ks[0], (d_model, d_inner + channels + self.heads)),
            "conv_w": jax.random.uniform(ks[1], (channels, self.conv),
                                         jnp.float32, -bound, bound),
            "conv_b": jax.random.uniform(ks[5], (channels,), jnp.float32,
                                         -bound, bound),
            "A_log": jnp.log(jax.random.uniform(
                ks[2], (self.heads,), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),    # softplus^-1(dt)
            "D": jnp.ones((self.heads,), jnp.float32),
            "norm": jnp.ones((d_inner,), jnp.float32),
            "out_proj": normal(ks[4], (d_inner, d_model)),
        }
        zero = jnp.zeros((), jnp.float32)
        return params, self._counters(zero, zero)

    def _apply(self, params, state, x, training, rng):
        # here and not at the top: importing bigdl_tpu.ops imports Pallas, 1.5 s
        # of every program's set-up that only a model with such a layer owes
        from ..ops.ssd import ssd_scan

        n, t, _ = x.shape
        d_inner, groups = self.heads * self.head_dim, self.groups
        bc = groups * self.state
        with jax.named_scope("ssm_proj"):
            z, xbc, dt = jnp.split(
                precision.dot_acc32(x, params["in_proj"]),
                [d_inner, 2 * d_inner + 2 * bc], axis=-1)
        with jax.named_scope("ssm_conv"):
            xbc = jax.nn.silu(causal_depthwise_conv(
                xbc, params["conv_w"], params["conv_b"]))
        with jax.named_scope("ssm_scan"):
            xs, b, c = jnp.split(xbc, [d_inner, d_inner + bc], axis=-1)
            y, stats = ssd_scan(
                xs.reshape(n, t, self.heads, self.head_dim),
                jax.nn.softplus(dt + params["dt_bias"]),
                -jnp.exp(params["A_log"]), b.reshape(n, t, groups, self.state),
                c.reshape(n, t, groups, self.state), params["D"], self.chunk)
            y = y.reshape(n, t, d_inner)
        with jax.named_scope("ssm_proj"):
            with jax.named_scope("ssm_gate_norm"):
                y = y * jax.nn.silu(z)
                if groups == 1:
                    y = self._norm._apply({"weight": params["norm"]}, {}, y,
                                          training, None)[0]
                else:   # the statistic over each group's channels, float32
                    yg = y.astype(jnp.float32).reshape(n, t, groups, -1)
                    ms = jnp.mean(yg * yg, axis=-1, keepdims=True)
                    y = ((yg * jax.lax.rsqrt(ms + self._norm.eps)).reshape(
                        n, t, d_inner) * params["norm"]).astype(y.dtype)
            out = precision.dot_acc32(y, params["out_proj"]).astype(x.dtype)
        return out, self._counters(
            stats.log_decay_min,
            jnp.sqrt(stats.state_sq_sum / stats.state_count))
