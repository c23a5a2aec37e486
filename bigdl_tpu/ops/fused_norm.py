"""Fused LayerNorm / RMSNorm Pallas kernels (forward + custom VJP).

The jnp normalization chain (mean → var → normalize → scale/shift) lowers to
several XLA ops whose fusion still round-trips the activation through HBM
more than once on the backward pass; these kernels do each pass in ONE
HBM round-trip per operand: a row block is loaded into VMEM, statistics are
computed in fp32 registers, and the normalized/scaled result (or the dx /
partial-dw/db contributions) is written straight back. The backward kernels
RECOMPUTE the row statistics from x in VMEM instead of saving normalized
activations — the same no-extra-residual design as ``ops.maxpool`` — so
enabling the fused path changes no residual memory.

Numerics: all statistics and the scale/shift math run in fp32 regardless of
the input dtype (the same policy ``nn.normalization`` documents for bf16
activations); LayerNorm returns fp32 (matching the jnp path's promotion
against its fp32 gain/bias), RMSNorm returns the input dtype (matching its
single narrowing cast). Weight/bias grads accumulate in fp32 across row
blocks via the sequential-grid revisited-output-block pattern.

Wired into ``nn.LayerNormalization`` / ``nn.RMSNorm`` behind
``Engine.set_fused_kernels(True)`` (see ``fused_common`` for the gate
semantics, including the CPU interpret mode tier-1 runs under). Parity vs
the jnp references and program-size thresholds are locked by
``tests/test_fused_kernels.py`` / ``tests/test_kernel_parity.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.compat import pallas_call
from .fused_common import block_rows, pad_rows

__all__ = ["fused_layer_norm", "fused_rms_norm"]


# --------------------------------------------------------------------------
# LayerNorm
# --------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (br, H)
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    xhat = xc * jax.lax.rsqrt(var + eps)
    y = xhat * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _ln_bwd_kernel(x_ref, w_ref, dy_ref, dx_ref, dw_ref, db_ref, *,
                   eps: float):
    """dx in closed form + fp32 dw/db partials accumulated across the
    sequential row-block grid (the same output block is revisited every
    step, so it stays resident in VMEM between iterations)."""
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    xhat = xc * r
    g = dy * w
    m1 = jnp.mean(g, axis=1, keepdims=True)
    m2 = jnp.mean(g * xhat, axis=1, keepdims=True)
    dx_ref[...] = (r * (g - m1 - xhat * m2)).astype(dx_ref.dtype)
    pdw = jnp.sum(dy * xhat, axis=0, keepdims=True)  # (1, H)
    pdb = jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = pdw
        db_ref[...] = pdb

    @pl.when(i != 0)
    def _accumulate():
        dw_ref[...] = dw_ref[...] + pdw
        db_ref[...] = db_ref[...] + pdb


def _ln_rows(x):
    h = x.shape[-1]
    return x.reshape(-1, h), h


def _ln_fwd_call(x, w, b, eps):
    x2, h = _ln_rows(x)
    br = block_rows(x2.shape[0], h * max(4, x.dtype.itemsize),
                    x.dtype.itemsize)
    x2, rows = pad_rows(x2, br)
    y = pallas_call(
        partial(_ln_fwd_kernel, eps=eps),
        grid=(x2.shape[0] // br,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
    )(x2, w.reshape(1, h), b.reshape(1, h))
    return y[:rows].reshape(x.shape[:-1] + (h,))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm(x, weight, bias, eps: float = 1e-5):
    """LayerNorm over the last dim, one fused kernel per pass.

    Semantics match ``nn.LayerNormalization``'s jnp chain: fp32 statistics,
    fp32 output (the gain/bias are fp32 masters)."""
    return _ln_fwd_call(x, weight, bias, eps)


def _ln_vjp_fwd(x, weight, bias, eps):
    return _ln_fwd_call(x, weight, bias, eps), (x, weight)


def _ln_vjp_bwd(eps, res, dy):
    x, w = res
    x2, h = _ln_rows(x)
    dy2 = dy.reshape(-1, h)
    br = block_rows(x2.shape[0], h * 4, x.dtype.itemsize, live_factor=10)
    x2, rows = pad_rows(x2, br)
    dy2, _ = pad_rows(dy2, br)  # zero cotangent rows: inert in every sum
    dx, dw, db = pallas_call(
        partial(_ln_bwd_kernel, eps=eps),
        grid=(x2.shape[0] // br,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((br, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # dw/db accumulate in order
        ),
    )(x2, w.reshape(1, h), dy2)
    return (
        dx[:rows].reshape(x.shape),
        dw.reshape(w.shape).astype(w.dtype),
        db.reshape(w.shape).astype(w.dtype),
    )


fused_layer_norm.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


def layer_norm_reference(x, weight, bias, eps: float = 1e-5):
    """The exact jnp chain ``nn.LayerNormalization`` runs — the parity oracle."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return y * weight + bias


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------

def _rms_fwd_kernel(x_ref, w_ref, y_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * w_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _rms_bwd_kernel(x_ref, w_ref, dy_ref, dx_ref, dw_ref, *, eps: float):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    h = x.shape[1]
    ms = jnp.mean(x * x, axis=1, keepdims=True)
    r = jax.lax.rsqrt(ms + eps)
    g = dy * w
    # d rsqrt(mean(x^2)+eps) / dx_j = -x_j r^3 / H
    dot = jnp.sum(g * x, axis=1, keepdims=True)
    dx = r * g - x * (r * r * r) * (dot / h)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    pdw = jnp.sum(dy * x * r, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = pdw

    @pl.when(i != 0)
    def _accumulate():
        dw_ref[...] = dw_ref[...] + pdw


def _rms_fwd_call(x, w, eps):
    x2, h = _ln_rows(x)
    br = block_rows(x2.shape[0], h * max(4, x.dtype.itemsize),
                    x.dtype.itemsize)
    x2, rows = pad_rows(x2, br)
    y = pallas_call(
        partial(_rms_fwd_kernel, eps=eps),
        grid=(x2.shape[0] // br,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
    )(x2, w.reshape(1, h))
    return y[:rows].reshape(x.shape)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def fused_rms_norm(x, weight, eps: float = 1e-6):
    """RMSNorm over the last dim, one fused kernel per pass.

    Semantics match ``nn.RMSNorm``: fp32 statistics and gain applied in fp32,
    one narrowing cast back to the input dtype at the end."""
    return _rms_fwd_call(x, weight, eps)


def _rms_vjp_fwd(x, weight, eps):
    return _rms_fwd_call(x, weight, eps), (x, weight)


def _rms_vjp_bwd(eps, res, dy):
    x, w = res
    x2, h = _ln_rows(x)
    dy2 = dy.reshape(-1, h)
    br = block_rows(x2.shape[0], h * 4, x.dtype.itemsize, live_factor=10)
    x2, rows = pad_rows(x2, br)
    dy2, _ = pad_rows(dy2, br)
    dx, dw = pallas_call(
        partial(_rms_bwd_kernel, eps=eps),
        grid=(x2.shape[0] // br,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((br, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
    )(x2, w.reshape(1, h), dy2)
    return (
        dx[:rows].reshape(x.shape),
        dw.reshape(w.shape).astype(w.dtype),
    )


fused_rms_norm.defvjp(_rms_vjp_fwd, _rms_vjp_bwd)


def rms_norm_reference(x, weight, eps: float = 1e-6):
    """The exact jnp chain ``nn.RMSNorm`` runs — the parity oracle."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps) * weight
    return y.astype(x.dtype)
