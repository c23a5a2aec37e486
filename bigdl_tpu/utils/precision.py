"""Mixed-precision policy — the TPU analog of the reference's native fp16 path.

Reference behavior (SURVEY.md §2.5): BigDL's only reduced precision is the wire
format — ``FP16CompressedTensor`` compresses gradients for the BlockManager
shuffle; compute is fp32 MKL. On TPU the MXU natively runs bf16 matmuls at 2x
the fp32 rate, so the policy lives in the COMPUTE path instead. Two tiers:

* **compute dtype** (default bf16 on TPU): each matmul/conv casts its OPERANDS
  to ``Engine.compute_dtype()``; the MXU accumulates partial products in fp32
  internally. Master params stay float32 always.
* **activation dtype** (opt-in via ``Engine.set_activation_dtype('bfloat16')``):
  what hot-op OUTPUTS keep. Default ``None`` = upcast every output back to
  float32 (exact residual stream, activations cross HBM at 4 B/elt). With the
  policy on, outputs stay bf16 — activations and their cotangents move at half
  the bytes, which is where ResNet-class models spend their HBM bandwidth.
  What stays float32 regardless: master params, optimizer slots, BN statistics
  (fp32 batch stats with a bf16 fused scale/shift apply — see
  nn/normalization.py), and the softmax/log-softmax/loss head (upcast at the
  head, a (B, classes) tensor — negligible traffic).

Every hot op routes through the helpers below; with ``compute_dtype == float32``
they are pass-throughs, so CPU tests see bit-identical fp32 math.

NOTE: both dtypes are read at TRACE time. Set them before building/jitting a
model; already-compiled functions keep the dtypes they were traced with.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .engine import Engine


def compute_dtype():
    """The operand dtype for MXU ops (jnp dtype); float32 means 'off'."""
    return jnp.dtype(Engine.compute_dtype())


def is_mixed() -> bool:
    return compute_dtype() != jnp.dtype(jnp.float32)


def out_dtype():
    """The dtype hot-op outputs keep: float32 unless the activation policy is on."""
    act = Engine.activation_dtype()
    return jnp.dtype(jnp.float32) if act is None else jnp.dtype(act)


def _cast(x, dt):
    return x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating) else x


def cast_compute(x):
    """Cast a float array to the compute dtype (identity when policy is fp32)."""
    dt = compute_dtype()
    return x if dt == jnp.dtype(jnp.float32) else _cast(x, dt)


def bias_add(y, b):
    """``y + b`` without silently promoting a reduced-precision activation:
    the fp32 master bias is cast to ``y``'s dtype so the add fuses into the
    producing matmul/conv epilogue instead of upcasting the whole tensor."""
    return y + _cast(b, y.dtype)


def _act_fn(act):
    """The jnp spelling of an epilogue activation name — ONE mapping, owned
    by ops/fused_epilogue (it doubles as the kernels' parity oracle)."""
    from ..ops.fused_epilogue import act_reference

    try:
        return act_reference(act)
    except KeyError:
        raise ValueError(
            f"unsupported epilogue activation {act!r} "
            "(expected relu|gelu|tanh|None)"
        ) from None


def bias_act(y, b, act=None):
    """Bias + activation epilogue over the TRAILING feature dim (``Linear``).

    ``act`` ∈ {None, 'relu', 'gelu', 'tanh'}; ``b=None`` means no bias
    (activation only — XLA fuses a bare elementwise op fine, no kernel).
    With ``act=None`` (or the fused-kernel switch off) this is exactly
    ``bias_add`` followed by the jnp activation — bit-identical to the
    pre-fusion path. Under ``Engine.set_fused_kernels(True)`` the whole
    epilogue runs as one ``ops.fused_epilogue`` kernel (fwd + custom VJP,
    docs/performance.md)."""
    fn = _act_fn(act)  # validates the name even on the bias-less paths
    if b is None:
        return y if act is None else fn(y)
    if act is None:
        return bias_add(y, b)
    if Engine.fused_kernels():
        from ..ops.fused_epilogue import fused_bias_act

        return fused_bias_act(y, b, act, -1)
    return fn(bias_add(y, b))


def channel_bias_act(y, b, act=None):
    """Bias + activation epilogue over the CHANNEL dim of an NCHW tensor
    (``SpatialConvolution``); ``b`` is the bare per-channel (C,) master bias
    (``None`` = no bias). Same contract as :func:`bias_act`."""
    fn = _act_fn(act)
    if b is None:
        return y if act is None else fn(y)
    fallback_b = b.reshape((1, -1) + (1,) * (y.ndim - 2))
    if act is None:
        return bias_add(y, fallback_b)
    if Engine.fused_kernels():
        from ..ops.fused_epilogue import fused_bias_act

        return fused_bias_act(y, b, act, 1)
    return fn(bias_add(y, fallback_b))


def to_float(x):
    """Upcast at a numerical head (softmax/log/loss): identity for fp32."""
    return _cast(x, jnp.float32)


def result_dtype(x_dtype):
    """Static-analysis mirror of the dtype a policy-routed matmul/conv returns
    for an ``x_dtype`` operand against fp32 master weights (see ``einsum``):
    ``out_dtype()`` under a mixed policy, plain jnp promotion otherwise.
    Used by the ``infer_shape`` contracts so ShapeProp agrees with
    ``jax.eval_shape`` bit-for-bit on dtypes."""
    if is_mixed():
        return out_dtype()
    return jnp.result_type(x_dtype, jnp.float32)


def einsum(subscripts: str, *operands):
    """jnp.einsum under the policy: bf16 compute, fp32 (or policy-dtype) result.

    The bf16 OUTPUT (upcast afterwards) rather than ``preferred_element_type``
    matters for two reasons: (a) the conv/dot transpose rules reject mixed
    fp32-cotangent/bf16-operand calls, and (b) a bf16 cotangent keeps the
    BACKWARD matmuls (2/3 of training FLOPs) on the bf16 MXU path instead of
    silently promoting them to fp32. The MXU still accumulates partial
    products in fp32 internally; only the tile outputs round to bf16.
    """
    dt = compute_dtype()
    if dt == jnp.dtype(jnp.float32):
        return jnp.einsum(subscripts, *operands)
    return jnp.einsum(subscripts, *(_cast(o, dt) for o in operands)).astype(
        out_dtype()
    )


def matmul(a, b):
    """a @ b under the policy (see ``einsum`` for the bf16-output rationale)."""
    dt = compute_dtype()
    if dt == jnp.dtype(jnp.float32):
        return a @ b
    return jnp.matmul(_cast(a, dt), _cast(b, dt)).astype(out_dtype())


def conv_general_dilated(x, w, **kwargs):
    """lax.conv_general_dilated under the policy (see ``einsum``)."""
    dt = compute_dtype()
    if dt == jnp.dtype(jnp.float32):
        return lax.conv_general_dilated(x, w, **kwargs)
    return lax.conv_general_dilated(_cast(x, dt), _cast(w, dt), **kwargs).astype(
        out_dtype()
    )


def _dot_acc32_impl(x, w):
    dt = compute_dtype()
    return jnp.dot(_cast(x, dt), _cast(w, dt),
                   preferred_element_type=jnp.float32)


@jax.custom_vjp
def dot_acc32(x, w):
    """``x (..., K) @ w (K, N)`` with operands in the compute dtype and a
    FLOAT32 result: the MXU's own accumulator, not rounded to bf16 on the way
    out as :func:`einsum`'s result is. Both backward products take their
    operands (the cotangent among them) in the compute dtype too and
    accumulate in float32, so the whole layer runs at "bf16 operands, float32
    accumulation" and not at whatever autodiff's transposes would promote to.
    With a float32 compute dtype this is ``x @ w`` and its plain gradient."""
    return _dot_acc32_impl(x, w)


def _dot_acc32_fwd(x, w):
    return _dot_acc32_impl(x, w), (x, w)


def _dot_acc32_bwd(res, g):
    x, w = res
    dt = compute_dtype()
    g = _cast(g, dt)
    dx = jnp.dot(g, _cast(w, dt).T, preferred_element_type=jnp.float32)
    k = x.shape[-1]
    dw = jnp.dot(_cast(x, dt).reshape(-1, k).T, g.reshape(-1, g.shape[-1]),
                 preferred_element_type=jnp.float32)
    return dx.astype(x.dtype), dw.astype(w.dtype)


dot_acc32.defvjp(_dot_acc32_fwd, _dot_acc32_bwd)
