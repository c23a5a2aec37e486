"""Fused bias + activation epilogue kernel (forward + custom VJP).

The hot layers' epilogue — add the fp32 master bias, apply the activation —
is elementwise, so XLA usually fuses it into the producing matmul/conv; what
it cannot fuse is the BACKWARD recomputation, where the activation derivative
re-reads the pre-activation from HBM next to the cotangent. This kernel does
fwd and bwd in one VMEM pass each, recomputing ``z = x + b`` on the fly (no
saved pre-activation residual — the maxpool/fused-norm design).

Supported activations: ``None`` (plain bias add), ``"relu"``, ``"gelu"``
(the tanh approximation — ``jax.nn.gelu(approximate=True)``), ``"tanh"``.
Two bias layouts cover the framework's epilogues:

* ``axis=-1`` — bias over the trailing feature dim (``nn.Linear``);
* ``axis=1`` — bias over the channel dim of an NCHW tensor
  (``nn.SpatialConvolution``): the tensor is VIEWED as (N*C, H*W) rows —
  a contiguous reshape, no transpose — with a per-ROW bias.

Wired through ``utils.precision.bias_act`` / ``channel_bias_act`` behind
``Engine.set_fused_kernels(True)``; with the switch off those helpers run
the exact pre-existing jnp path (bit-identical — test-locked).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.compat import pallas_call
from .fused_common import block_rows, pad_rows

__all__ = ["fused_bias_act", "ACTIVATIONS", "act_reference"]

ACTIVATIONS = (None, "relu", "gelu", "tanh")

_GELU_C = math.sqrt(2.0 / math.pi)


def _act_f32(z, act: Optional[str]):
    if act is None:
        return z
    if act == "relu":
        return jnp.maximum(z, 0.0)
    if act == "tanh":
        return jnp.tanh(z)
    if act == "gelu":
        u = _GELU_C * (z + 0.044715 * z * z * z)
        return 0.5 * z * (1.0 + jnp.tanh(u))
    raise ValueError(f"unsupported fused activation {act!r}")


def _act_grad_f32(z, act: Optional[str]):
    if act is None:
        return jnp.ones_like(z)
    if act == "relu":
        return (z > 0.0).astype(z.dtype)
    if act == "tanh":
        t = jnp.tanh(z)
        return 1.0 - t * t
    if act == "gelu":
        u = _GELU_C * (z + 0.044715 * z * z * z)
        t = jnp.tanh(u)
        du = _GELU_C * (1.0 + 3.0 * 0.044715 * z * z)
        return 0.5 * (1.0 + t) + 0.5 * z * (1.0 - t * t) * du
    raise ValueError(f"unsupported fused activation {act!r}")


def act_reference(act: Optional[str]):
    """The jnp activation each kernel name mirrors — the parity oracle."""
    return {
        None: lambda z: z,
        "relu": lambda z: jnp.maximum(z, 0),
        "gelu": lambda z: jax.nn.gelu(z, approximate=True),
        "tanh": jnp.tanh,
    }[act]


# --------------------------------------------------------------------------
# kernels (feature mode: bias broadcast over rows; row mode: bias per row)
# --------------------------------------------------------------------------

def _fwd_kernel(x_ref, b_ref, y_ref, *, act):
    z = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = _act_f32(z, act).astype(y_ref.dtype)


def _bwd_feature_kernel(x_ref, b_ref, dy_ref, dx_ref, db_ref, *, act):
    i = pl.program_id(0)
    z = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    dz = dy_ref[...].astype(jnp.float32) * _act_grad_f32(z, act)
    dx_ref[...] = dz.astype(dx_ref.dtype)
    pdb = jnp.sum(dz, axis=0, keepdims=True)  # (1, H)

    @pl.when(i == 0)
    def _init():
        db_ref[...] = pdb

    @pl.when(i != 0)
    def _accumulate():
        db_ref[...] = db_ref[...] + pdb


def _bwd_row_kernel(x_ref, b_ref, dy_ref, dx_ref, db_ref, *, act):
    z = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    dz = dy_ref[...].astype(jnp.float32) * _act_grad_f32(z, act)
    dx_ref[...] = dz.astype(dx_ref.dtype)
    # per-row partial; the (N, C) -> (C,) fold happens outside (tiny)
    db_ref[...] = jnp.sum(dz, axis=1, keepdims=True)  # (br, 1)


# --------------------------------------------------------------------------
# wrappers
# --------------------------------------------------------------------------

def _as_rows(x, axis: int):
    """(rows, features) view + the per-row/per-feature bias expander."""
    if axis in (-1, x.ndim - 1):
        h = x.shape[-1]
        return x.reshape(-1, h), h, "feature"
    if axis == 1:
        n, c = x.shape[0], x.shape[1]
        feat = 1
        for d in x.shape[2:]:
            feat *= d
        return x.reshape(n * c, feat), feat, "row"
    raise ValueError(f"fused_bias_act supports axis -1 or 1, got {axis}")


def _bias_rows(x, b, mode: str):
    if mode == "feature":
        return b.reshape(1, -1)
    n, c = x.shape[0], x.shape[1]
    return jnp.tile(b.reshape(1, c), (n, 1)).reshape(n * c, 1)


def _fwd_call(x, b, act, axis):
    x2, h, mode = _as_rows(x, axis)
    b2 = _bias_rows(x, b, mode)
    br = block_rows(x2.shape[0], h * max(4, x.dtype.itemsize),
                    x.dtype.itemsize, live_factor=6)
    x2, rows = pad_rows(x2, br)
    if mode == "row":
        b2, _ = pad_rows(b2, br)
        b_spec = pl.BlockSpec((br, 1), lambda i: (i, 0))
    else:
        b_spec = pl.BlockSpec((1, h), lambda i: (0, 0))
    y = pallas_call(
        partial(_fwd_kernel, act=act),
        grid=(x2.shape[0] // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)), b_spec],
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
    )(x2, b2)
    return y[:rows].reshape(x.shape)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_bias_act(x, bias, act: Optional[str] = None, axis: int = -1):
    """``act(x + bias)`` in one fused pass; bias broadcast along ``axis``.

    Output keeps ``x``'s dtype (the epilogue contract ``precision.bias_add``
    documents: the fp32 master bias is cast in, never the tensor up)."""
    return _fwd_call(x, bias, act, axis)


def _vjp_fwd(x, bias, act, axis):
    return _fwd_call(x, bias, act, axis), (x, bias)


def _vjp_bwd(act, axis, res, dy):
    x, b = res
    x2, h, mode = _as_rows(x, axis)
    dy2 = dy.reshape(x2.shape)
    b2 = _bias_rows(x, b, mode)
    br = block_rows(x2.shape[0], h * 4, x.dtype.itemsize, live_factor=8)
    x2, rows = pad_rows(x2, br)
    dy2, _ = pad_rows(dy2, br)
    if mode == "row":
        b2, _ = pad_rows(b2, br)
        b_spec = pl.BlockSpec((br, 1), lambda i: (i, 0))
        db_spec = pl.BlockSpec((br, 1), lambda i: (i, 0))
        db_shape = jax.ShapeDtypeStruct((x2.shape[0], 1), jnp.float32)
        semantics = ("parallel",)
    else:
        b_spec = pl.BlockSpec((1, h), lambda i: (0, 0))
        db_spec = pl.BlockSpec((1, h), lambda i: (0, 0))
        db_shape = jax.ShapeDtypeStruct((1, h), jnp.float32)
        semantics = ("arbitrary",)  # db accumulates across row blocks
    kernel = _bwd_feature_kernel if mode == "feature" else _bwd_row_kernel
    dx, db = pallas_call(
        partial(kernel, act=act),
        grid=(x2.shape[0] // br,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            b_spec,
            pl.BlockSpec((br, h), lambda i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)), db_spec],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x.dtype), db_shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
        ),
    )(x2, b2, dy2)
    dx = dx[:rows].reshape(x.shape)
    if mode == "feature":
        db_out = db.reshape(-1)
    else:
        n, c = x.shape[0], x.shape[1]
        db_out = jnp.sum(db[:rows].reshape(n, c), axis=0)
    return dx, db_out.astype(b.dtype).reshape(b.shape)


fused_bias_act.defvjp(_vjp_fwd, _vjp_bwd)
