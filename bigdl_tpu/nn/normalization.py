"""Normalization layers (reference: ``$DL/nn/SpatialBatchNormalization.scala``,
``BatchNormalization.scala``, ``SpatialCrossMapLRN.scala``, ``Normalize.scala``).

BN running mean/var are the canonical "module state": they live in the state
pytree (the reference stores them as extraParameters), updated under jit during
training. The reference's BN stats are per-replica in distributed runs;
DistriOptimizer cross-replica-averages the state each step (documented deviation).

Reference defaults preserved: eps=1e-5, momentum=0.1 (new = (1-m)*old + m*batch).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .module import AbstractModule


class BatchNormalization(AbstractModule):
    """BN over (N, C) or (N, C, ...) with C at dim 1 (reference: BatchNormalization).

    ``affine`` adds learnable weight (gamma) / bias (beta).
    """

    def __init__(
        self,
        n_output: Optional[int] = None,
        eps: float = 1e-5,
        momentum: float = 0.1,
        affine: bool = True,
    ):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.momentum = momentum
        self.affine = affine

    def _channel_axis(self, x) -> int:
        return 1

    def infer_shape(self, in_spec):
        shape = tuple(in_spec.shape)
        ax = self._channel_axis(in_spec)
        if len(shape) <= ax:
            raise ValueError(
                f"{self.name()}: needs a channel dim at axis {ax}, got shape {shape}"
            )
        c = shape[ax]
        if self.n_output is not None and c != self.n_output:
            raise ValueError(
                f"{self.name()}: expected {self.n_output} channels, got {c} "
                f"(input shape {shape})"
            )
        return jax.ShapeDtypeStruct(shape, in_spec.dtype)

    def _build(self, rng, in_spec):
        c = in_spec.shape[self._channel_axis(in_spec)]
        if self.n_output is not None and self.n_output != c:
            raise ValueError(f"{self.name()}: expected {self.n_output} channels, got {c}")
        self.n_output = c
        params = {}
        if self.affine:
            params = {"weight": jnp.ones((c,)), "bias": jnp.zeros((c,))}
        state = {"running_mean": jnp.zeros((c,)), "running_var": jnp.ones((c,))}
        return params, state

    def _apply(self, params, state, x, training, rng):
        ax = self._channel_axis(x)
        reduce_axes = tuple(i for i in range(x.ndim) if i != ax)
        shape = [1] * x.ndim
        shape[ax] = x.shape[ax]
        # statistics are ALWAYS float32, even when the activation policy keeps
        # x in bf16 (a bf16 mean over 100k+ elements loses whole digits)
        xf = x if x.dtype == jnp.float32 else x.astype(jnp.float32)
        if training:
            mean = jnp.mean(xf, axis=reduce_axes)
            var = jnp.var(xf, axis=reduce_axes)
            m = self.momentum
            n = x.size / x.shape[ax]
            unbiased = var * n / max(n - 1, 1)
            new_state = {
                "running_mean": (1 - m) * state["running_mean"] + m * mean,
                "running_var": (1 - m) * state["running_var"] + m * unbiased,
            }
        else:
            mean, var = state["running_mean"], state["running_var"]
            new_state = state
        if x.dtype == jnp.float32:
            y = (x - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + self.eps)
            if self.affine:
                y = y * params["weight"].reshape(shape) + params["bias"].reshape(shape)
        else:
            # reduced-precision activations: fold (mean, var, gamma, beta) into
            # one fp32 per-channel (scale, shift), then apply in x's dtype so
            # the output stays on the policy's narrow residual stream
            scale = jax.lax.rsqrt(var + self.eps)
            if self.affine:
                scale = scale * params["weight"]
                shift = params["bias"] - mean * scale
            else:
                shift = -mean * scale
            y = x * scale.reshape(shape).astype(x.dtype) + shift.reshape(shape).astype(x.dtype)
        return y, new_state


class SpatialBatchNormalization(BatchNormalization):
    """BN over NCHW, per-channel stats (reference: SpatialBatchNormalization)."""


class LayerNormalization(AbstractModule):
    """LayerNorm over the last dim (reference: $DL/nn/LayerNormalization.scala)."""

    def __init__(self, hidden_size: Optional[int] = None, eps: float = 1e-5):
        super().__init__()
        self.hidden_size = hidden_size
        self.eps = eps

    def infer_shape(self, in_spec):
        shape = tuple(in_spec.shape)
        if self.hidden_size is not None and shape[-1] != self.hidden_size:
            raise ValueError(
                f"{self.name()}: declared hidden size {self.hidden_size}, got "
                f"last dim {shape[-1]} (input shape {shape})"
            )
        return jax.ShapeDtypeStruct(
            shape, jnp.result_type(in_spec.dtype, jnp.float32)
        )

    def _build(self, rng, in_spec):
        h = in_spec.shape[-1]
        if self.hidden_size is not None and self.hidden_size != h:
            raise ValueError(
                f"{self.name()}: declared hidden size {self.hidden_size}, got {h}"
            )
        self.hidden_size = h
        return {"weight": jnp.ones((h,)), "bias": jnp.zeros((h,))}, {}

    def _apply(self, params, state, x, training, rng):
        from ..utils.engine import Engine

        if Engine.fused_kernels():
            # one HBM round-trip per pass (fwd + custom VJP) instead of the
            # mean/var/normalize/scale chain; Engine.set_fused_kernels gates
            # this at trace time — off, the path below is bit-identical to
            # every prior build (docs/performance.md)
            from ..ops.fused_norm import fused_layer_norm

            return (
                fused_layer_norm(x, params["weight"], params["bias"],
                                 self.eps),
                state,
            )
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.eps)
        return y * params["weight"] + params["bias"], state


class RMSNorm(AbstractModule):
    """Root-mean-square norm over the last dim (Zhang & Sennrich 2019) —
    LayerNorm without centering or bias: ``x * rsqrt(mean(x^2)+eps) * g``.
    The modern-LM norm (pairs with rope/swiglu); beyond reference.
    Statistics in fp32 regardless of the activation dtype (the same
    policy BatchNorm uses under the bf16 activation mode)."""

    def __init__(self, hidden_size: Optional[int] = None, eps: float = 1e-6):
        super().__init__()
        self.hidden_size = hidden_size
        self.eps = eps

    def infer_shape(self, in_spec):
        shape = tuple(in_spec.shape)
        if self.hidden_size is not None and shape[-1] != self.hidden_size:
            raise ValueError(
                f"{self.name()}: declared hidden size {self.hidden_size}, got "
                f"last dim {shape[-1]} (input shape {shape})"
            )
        return jax.ShapeDtypeStruct(shape, in_spec.dtype)

    def _build(self, rng, in_spec):
        h = in_spec.shape[-1]
        if self.hidden_size is not None and self.hidden_size != h:
            raise ValueError(
                f"{self.name()}: declared hidden size {self.hidden_size}, got {h}"
            )
        self.hidden_size = h
        return {"weight": jnp.ones((h,))}, {}

    def _apply(self, params, state, x, training, rng):
        from ..utils.engine import Engine

        if Engine.fused_kernels():
            from ..ops.fused_norm import fused_rms_norm

            return fused_rms_norm(x, params["weight"], self.eps), state
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        # apply the (fp32) gain BEFORE the single narrowing cast — casting
        # first and then multiplying by a float32 param would silently
        # promote the output back to fp32 and widen the residual stream
        # (r5 review finding)
        y = xf * jax.lax.rsqrt(ms + self.eps) * params["weight"]
        return y.astype(x.dtype), state


class SpatialCrossMapLRN(AbstractModule):
    """Local response norm across channels (reference: SpatialCrossMapLRN; AlexNet).

    y = x / (k + alpha/size * sum_{local window} x^2)^beta
    """

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75, k: float = 1.0):
        super().__init__()
        self.size = size
        self.alpha = alpha
        self.beta = beta
        self.k = k

    infer_shape = AbstractModule._infer_shape_via_apply  # parameter-less

    def _apply(self, params, state, x, training, rng):
        sq = x * x
        half = self.size // 2
        # sum over a channel window via padded reduce_window on dim 1
        summed = jax.lax.reduce_window(
            sq,
            0.0,
            jax.lax.add,
            window_dimensions=(1, self.size, 1, 1),
            window_strides=(1, 1, 1, 1),
            padding=[(0, 0), (half, self.size - 1 - half), (0, 0), (0, 0)],
        )
        denom = (self.k + self.alpha / self.size * summed) ** self.beta
        return x / denom, state


class Normalize(AbstractModule):
    """Lp-normalize over the feature dim (reference: $DL/nn/Normalize.scala)."""

    def __init__(self, p: float = 2.0, eps: float = 1e-10):
        super().__init__()
        self.p = p
        self.eps = eps

    infer_shape = AbstractModule._infer_shape_via_apply  # parameter-less

    def _apply(self, params, state, x, training, rng):
        if self.p == float("inf"):
            norm = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        else:
            norm = jnp.sum(jnp.abs(x) ** self.p, axis=-1, keepdims=True) ** (1.0 / self.p)
        return x / (norm + self.eps), state


class SpatialWithinChannelLRN(AbstractModule):
    """LRN within channel over spatial window (reference: SpatialWithinChannelLRN)."""

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75):
        super().__init__()
        self.size = size
        self.alpha = alpha
        self.beta = beta

    infer_shape = AbstractModule._infer_shape_via_apply  # parameter-less

    def _apply(self, params, state, x, training, rng):
        sq = x * x
        half = self.size // 2
        summed = jax.lax.reduce_window(
            sq,
            0.0,
            jax.lax.add,
            window_dimensions=(1, 1, self.size, self.size),
            window_strides=(1, 1, 1, 1),
            padding=[(0, 0), (0, 0), (half, self.size - 1 - half), (half, self.size - 1 - half)],
        )
        denom = (1.0 + self.alpha / (self.size * self.size) * summed) ** self.beta
        return x / denom, state
