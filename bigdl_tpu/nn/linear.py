"""Dense layers (reference: ``$DL/nn/Linear.scala``, ``$DL/nn/Bilinear.scala``...).

The reference hand-writes forward (MKL gemm) and backward (two more gemms). Here the
forward is one ``jnp`` expression that XLA maps onto the MXU; backward is derived.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..utils import precision
from .initialization import InitializationMethod, RandomUniform, Zeros
from .module import AbstractModule, Container, run_child


class Linear(AbstractModule):
    """y = x W^T + b over the last dim; batches over leading dims.

    Reference: ``Linear(inputSize, outputSize, withBias, wRegularizer, bRegularizer)``
    in $DL/nn/Linear.scala. ``input_size`` may be omitted (lazy shape inference).
    """

    def __init__(
        self,
        input_size: Optional[int] = None,
        output_size: int = 0,
        with_bias: bool = True,
        w_regularizer=None,
        b_regularizer=None,
        activation: Optional[str] = None,
    ):
        super().__init__()
        self.input_size = input_size
        self.output_size = output_size
        self.with_bias = with_bias
        self.w_regularizer = w_regularizer
        self.b_regularizer = b_regularizer
        # optional built-in epilogue (relu|gelu|tanh): declared here — rather
        # than as a following activation module — it rides the fused
        # bias+activation kernel under Engine.set_fused_kernels(True); the
        # default (None) leaves the layer exactly as before
        self.activation = activation
        self.weight_init: InitializationMethod = RandomUniform()
        self.bias_init: InitializationMethod = RandomUniform()

    def set_init_method(self, weight_init=None, bias_init=None) -> "Linear":
        if weight_init is not None:
            self.weight_init = weight_init
        if bias_init is not None:
            self.bias_init = bias_init
        return self

    def _build(self, rng, in_spec):
        in_size = in_spec.shape[-1]
        if self.input_size is not None and self.input_size != in_size:
            raise ValueError(
                f"{self.name()}: expected last dim {self.input_size}, got {in_size}"
            )
        self.input_size = in_size
        kw, kb = jax.random.split(rng)
        # weight stored (out, in) — Torch convention, matches reference serialization
        params = {
            "weight": self.weight_init(
                kw, (self.output_size, in_size), in_size, self.output_size
            )
        }
        if self.with_bias:
            params["bias"] = self.bias_init(
                kb, (self.output_size,), in_size, self.output_size
            )
        return params, {}

    def infer_shape(self, in_spec):
        shape = tuple(in_spec.shape)
        if not shape:
            raise ValueError(
                f"{self.name()}: needs a trailing feature dim, got a scalar input"
            )
        if self.input_size is not None and shape[-1] != self.input_size:
            raise ValueError(
                f"{self.name()}: expected last dim {self.input_size}, got "
                f"{shape[-1]} (input shape {shape})"
            )
        from ..tensor.sparse import SparseTensor

        dt = in_spec.values.dtype if isinstance(in_spec, SparseTensor) else in_spec.dtype
        return jax.ShapeDtypeStruct(
            shape[:-1] + (self.output_size,), precision.result_dtype(dt)
        )

    def _apply(self, params, state, x, training, rng):
        y = precision.einsum("...i,oi->...o", x, params["weight"])
        return precision.bias_act(
            y, params["bias"] if self.with_bias else None, self.activation
        ), state

    def regularization_loss(self, params):
        loss = 0.0
        if self.w_regularizer is not None:
            loss = loss + self.w_regularizer(params["weight"])
        if self.b_regularizer is not None and self.with_bias:
            loss = loss + self.b_regularizer(params["bias"])
        return loss


class SparseLinear(Linear):
    """Linear over a host-side SparseTensor input (reference: $DL/nn/SparseLinear.scala).

    TPU-native: the sparse input arrives as a ``SparseTensor`` (COO pytree); the
    product gathers embedding rows of W via ``take`` + ``segment_sum`` — the MXU-free
    path appropriate for very wide sparse features (wide&deep's wide column).
    """

    def _apply(self, params, state, x, training, rng):
        from ..tensor.sparse import SparseTensor

        if not isinstance(x, SparseTensor):
            return super()._apply(params, state, x, training, rng)
        # rows: batch index; cols: feature index; vals: feature value
        w = params["weight"]  # (out, in)
        contrib = w[:, x.col_indices].T * x.values[:, None]  # (nnz, out)
        y = jax.ops.segment_sum(contrib, x.row_indices, num_segments=x.shape[0])
        return precision.bias_act(
            y, params["bias"] if self.with_bias else None, self.activation
        ), state


class Maxout(Container):
    """maxout unit: Linear to (out x pool) then max over the pool (reference:
    ``$DL/nn/Maxout.scala`` — keras ``MaxoutDense``)."""

    def __init__(self, input_size: Optional[int], output_size: int,
                 maxout_number: int, with_bias: bool = True,
                 w_regularizer=None, b_regularizer=None):
        self.output_size = output_size
        self.maxout_number = maxout_number
        super().__init__(Linear(input_size, output_size * maxout_number,
                                with_bias, w_regularizer, b_regularizer))

    def build(self, rng, in_spec):
        s = self.modules[0].build(rng, in_spec)
        self._built = True
        return jax.ShapeDtypeStruct(s.shape[:-1] + (self.output_size,), s.dtype)

    def infer_shape(self, in_spec):
        from .module import infer_module_shape

        s = infer_module_shape(self.modules[0], in_spec)
        return jax.ShapeDtypeStruct(s.shape[:-1] + (self.output_size,), s.dtype)

    def _apply(self, params, state, x, training, rng):
        lin = self.modules[0]
        y, s = run_child(lin, params[lin.name()], state[lin.name()], x, training, rng)
        y = y.reshape(*y.shape[:-1], self.maxout_number, self.output_size)
        return jnp.max(y, axis=-2), {lin.name(): s}


class Highway(Container):
    """Highway unit: y = T(x) * H(x) + (1 - T(x)) * x (reference: keras
    ``Highway.scala``; gate bias initialized negative so early training
    passes the input through)."""

    def __init__(self, size: Optional[int] = None, with_bias: bool = True,
                 activation=None, w_regularizer=None, b_regularizer=None):
        super().__init__()
        self.size = size
        self.with_bias = with_bias
        self.regs = (w_regularizer, b_regularizer)
        self.activation = activation

    def build(self, rng, in_spec):
        size = self.size if self.size is not None else in_spec.shape[-1]
        if not self.modules:  # size=None defers child creation to build
            self.add(Linear(size, size, self.with_bias, *self.regs))
            self.add(Linear(size, size, self.with_bias, *self.regs))
        k1, k2 = jax.random.split(rng)
        h, t = self.modules
        out = h.build(k1, in_spec)
        t.build(k2, in_spec)
        tp = t.get_parameters()
        if "bias" in tp:
            t.set_parameters(dict(tp, bias=tp["bias"] - 2.0))  # carry-biased
        self._built = True
        return out

    def infer_shape(self, in_spec):
        shape = tuple(in_spec.shape)
        if self.size is not None and shape[-1] != self.size:
            raise ValueError(
                f"{self.name()}: declared size {self.size}, got last dim "
                f"{shape[-1]} (input shape {shape})"
            )
        # gate*H(x) + (1-gate)*x — shape-preserving; dtype promotes into the
        # Linear towers' output
        dt = jnp.result_type(precision.result_dtype(in_spec.dtype), in_spec.dtype)
        return jax.ShapeDtypeStruct(shape, dt)

    def _apply(self, params, state, x, training, rng):
        hm, tm = self.modules
        h, hs = run_child(hm, params[hm.name()], state[hm.name()], x, training, rng)
        if self.activation is not None:
            h = self.activation(h)
        t, ts = run_child(tm, params[tm.name()], state[tm.name()], x, training, rng)
        gate = 1.0 / (1.0 + jnp.exp(-t))
        return gate * h + (1.0 - gate) * x, {hm.name(): hs, tm.name(): ts}
