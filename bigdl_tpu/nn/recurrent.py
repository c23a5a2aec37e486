"""Recurrent layers (reference: ``$DL/nn/Recurrent.scala``, ``Cell.scala``,
``LSTM.scala``, ``LSTMPeephole.scala``, ``GRU.scala``, ``RnnCell.scala``,
``BiRecurrent.scala``, ``TimeDistributed.scala``, ``RecurrentDecoder.scala``).

Reference behavior: ``Recurrent`` drives a sequential Scala time loop, cloning
the cell per step with shared weights and threading a hidden-state Table.

TPU-native design — the single biggest RNN rework: the time loop is
``jax.lax.scan`` over the cell's pure step function. Weights are naturally
shared (one param set, closed over by the scan body); XLA unrolls nothing —
it compiles one step and loops on-device, which is exactly the memory/compute
shape the MXU wants. Input layout is batch-first (N, T, D), Torch convention.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import precision
from ..utils.table import T, Table
from .initialization import InitializationMethod, RandomUniform
from .module import AbstractModule, Container, child_scope


class Cell(AbstractModule):
    """Recurrent cell base: ``step(params, carry, x_t) -> (new_carry, y_t)``.

    ``init_carry(batch)`` builds the zero hidden state. ``hidden_size`` is the
    output width per step.
    """

    accepts_table_input = True  # consumes a multi-parent Table when graph-wired

    hidden_size: int

    def init_carry(self, batch_size: int):
        raise NotImplementedError

    def step(self, params, carry, x_t):
        raise NotImplementedError

    def _apply(self, params, state, x, training, rng):
        # a bare cell applied outside Recurrent processes ONE step from the zero
        # carry; hidden-state threading across steps is Recurrent's job
        _, y = self.step(params, self.init_carry(x.shape[0]), x)
        return y, state


class RnnCell(Cell):
    """tanh(W x + U h + b) (reference: RnnCell)."""

    def __init__(self, input_size: Optional[int], hidden_size: int, activation=jnp.tanh):
        super().__init__()
        self.input_size = input_size
        self.hidden_size = hidden_size
        self.activation = activation
        self.weight_init: InitializationMethod = RandomUniform()

    def init_carry(self, batch_size: int):
        return jnp.zeros((batch_size, self.hidden_size))

    def _build(self, rng, in_spec):
        d = in_spec.shape[-1]
        if self.input_size is not None and self.input_size != d:
            raise ValueError(
                f"{self.name()}: declared input_size {self.input_size}, got {d}"
            )
        self.input_size = d
        h = self.hidden_size
        k1, k2, k3 = jax.random.split(rng, 3)
        return {
            "i2h": self.weight_init(k1, (h, d), d, h),
            "h2h": self.weight_init(k2, (h, h), h, h),
            "bias": self.weight_init(k3, (h,), d, h),
        }, {}

    def step(self, params, carry, x_t):
        h = self.activation(
            precision.matmul(x_t, params["i2h"].T) + precision.matmul(carry, params["h2h"].T) + params["bias"]
        )
        return h, h


class LSTM(Cell):
    """Standard LSTM cell (reference: $DL/nn/LSTM.scala).

    Gate order i, f, g(candidate), o packed into one (4H, D)/(4H, H) matmul pair
    — one big MXU-friendly gemm per step instead of eight small ones.
    """

    def __init__(self, input_size: Optional[int], hidden_size: int,
                 w_regularizer=None, u_regularizer=None, b_regularizer=None):
        super().__init__()
        self.input_size = input_size
        self.hidden_size = hidden_size
        self.w_regularizer = w_regularizer
        self.u_regularizer = u_regularizer
        self.b_regularizer = b_regularizer
        self.weight_init: InitializationMethod = RandomUniform()

    def init_carry(self, batch_size: int):
        h = jnp.zeros((batch_size, self.hidden_size))
        return (h, jnp.zeros_like(h))

    def _build(self, rng, in_spec):
        d = in_spec.shape[-1]
        if self.input_size is not None and self.input_size != d:
            raise ValueError(
                f"{self.name()}: declared input_size {self.input_size}, got {d}"
            )
        self.input_size = d
        hsz = self.hidden_size
        k1, k2, k3 = jax.random.split(rng, 3)
        return {
            "i2g": self.weight_init(k1, (4 * hsz, d), d, hsz),
            "h2g": self.weight_init(k2, (4 * hsz, hsz), hsz, hsz),
            "bias": self.weight_init(k3, (4 * hsz,), d, hsz),
        }, {}

    def step(self, params, carry, x_t):
        h, c = carry
        gates = precision.matmul(x_t, params["i2g"].T) + precision.matmul(h, params["h2g"].T) + params["bias"]
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        new_c = f * c + i * g
        new_h = o * jnp.tanh(new_c)
        return (new_h, new_c), new_h

    def regularization_loss(self, params):
        loss = 0.0
        if self.w_regularizer is not None:
            loss = loss + self.w_regularizer(params["i2g"])
        if self.u_regularizer is not None:
            loss = loss + self.u_regularizer(params["h2g"])
        if self.b_regularizer is not None:
            loss = loss + self.b_regularizer(params["bias"])
        return loss


class LSTMPeephole(LSTM):
    """LSTM with peephole connections c→gates (reference: LSTMPeephole)."""

    def _build(self, rng, in_spec):
        params, state = super()._build(rng, in_spec)
        k = jax.random.fold_in(rng, 99)
        hsz = self.hidden_size
        params["peep"] = self.weight_init(k, (3, hsz), hsz, hsz)
        return params, state

    def step(self, params, carry, x_t):
        h, c = carry
        gates = precision.matmul(x_t, params["i2g"].T) + precision.matmul(h, params["h2g"].T) + params["bias"]
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        p = params["peep"]
        i = jax.nn.sigmoid(i + p[0] * c)
        f = jax.nn.sigmoid(f + p[1] * c)
        g = jnp.tanh(g)
        new_c = f * c + i * g
        o = jax.nn.sigmoid(o + p[2] * new_c)
        new_h = o * jnp.tanh(new_c)
        return (new_h, new_c), new_h


class GRU(Cell):
    """GRU cell (reference: $DL/nn/GRU.scala)."""

    def __init__(self, input_size: Optional[int], hidden_size: int):
        super().__init__()
        self.input_size = input_size
        self.hidden_size = hidden_size
        self.weight_init: InitializationMethod = RandomUniform()

    def init_carry(self, batch_size: int):
        return jnp.zeros((batch_size, self.hidden_size))

    def _build(self, rng, in_spec):
        d = in_spec.shape[-1]
        if self.input_size is not None and self.input_size != d:
            raise ValueError(
                f"{self.name()}: declared input_size {self.input_size}, got {d}"
            )
        self.input_size = d
        hsz = self.hidden_size
        k1, k2, k3, k4, k5, k6 = jax.random.split(rng, 6)
        return {
            "i2rz": self.weight_init(k1, (2 * hsz, d), d, hsz),
            "h2rz": self.weight_init(k2, (2 * hsz, hsz), hsz, hsz),
            "bias_rz": self.weight_init(k3, (2 * hsz,), d, hsz),
            "i2n": self.weight_init(k4, (hsz, d), d, hsz),
            "h2n": self.weight_init(k5, (hsz, hsz), hsz, hsz),
            "bias_n": self.weight_init(k6, (hsz,), d, hsz),
        }, {}

    def step(self, params, carry, x_t):
        rz = jax.nn.sigmoid(
            precision.matmul(x_t, params["i2rz"].T) + precision.matmul(carry, params["h2rz"].T) + params["bias_rz"]
        )
        r, z = jnp.split(rz, 2, axis=-1)
        n = jnp.tanh(precision.matmul(x_t, params["i2n"].T) + r * precision.matmul(carry, params["h2n"].T) + params["bias_n"])
        new_h = (1 - z) * n + z * carry
        return new_h, new_h


class ConvLSTMPeephole(Cell):
    """Convolutional LSTM cell with peephole connections over (N, C, H, W)
    steps (reference: ``$DL/nn/ConvLSTMPeephole.scala``).

    The gate matmuls of LSTM become SAME-padded convolutions (hidden state must
    keep its spatial dims for the recurrence); peepholes are per-channel
    elementwise weights on the cell state. Drive with ``Recurrent`` over
    (N, T, C, H, W) input — `lax.scan` compiles one conv step and loops
    on-device.
    """

    def __init__(
        self,
        input_size: Optional[int],
        output_size: int,
        kernel_i: int = 3,
        kernel_c: int = 3,
        stride: int = 1,
        with_peephole: bool = True,
    ):
        super().__init__()
        if stride != 1:
            raise ValueError(
                "ConvLSTMPeephole requires stride 1 (hidden spatial dims must "
                "be preserved across steps)"
            )
        self.input_size = input_size
        self.hidden_size = output_size  # channels; Recurrent infers full shape
        self.output_size = output_size
        self.kernel_i = kernel_i
        self.kernel_c = kernel_c
        self.with_peephole = with_peephole
        self.weight_init: InitializationMethod = RandomUniform()
        self._spatial: Optional[Tuple[int, int]] = None

    def init_carry(self, batch_size: int):
        if self._spatial is None:
            raise ValueError("ConvLSTMPeephole: build before init_carry")
        h, w = self._spatial
        z = jnp.zeros((batch_size, self.output_size, h, w))
        return (z, jnp.zeros_like(z))

    def _build(self, rng, in_spec):
        cin = in_spec.shape[1]
        if self.input_size is not None and self.input_size != cin:
            raise ValueError(
                f"{self.name()}: declared input_size {self.input_size}, got {cin}"
            )
        self.input_size = cin
        self._spatial = (in_spec.shape[2], in_spec.shape[3])
        co = self.output_size
        ki, kc = self.kernel_i, self.kernel_c
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        fan_i, fan_c = cin * ki * ki, co * kc * kc
        params = {
            "i2g": self.weight_init(k1, (4 * co, cin, ki, ki), fan_i, co),
            "h2g": self.weight_init(k2, (4 * co, co, kc, kc), fan_c, co),
            "bias": self.weight_init(k3, (4 * co,), fan_i, co),
        }
        if self.with_peephole:
            params["peep"] = self.weight_init(k4, (3, co), co, co)
        return params, {}

    def step(self, params, carry, x_t):
        from ..utils import precision

        h, c = carry
        gates = (
            precision.conv_general_dilated(
                x_t, params["i2g"], window_strides=(1, 1), padding="SAME",
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
            )
            + precision.conv_general_dilated(
                h, params["h2g"], window_strides=(1, 1), padding="SAME",
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
            )
            + params["bias"][None, :, None, None]
        )
        i, f, g, o = jnp.split(gates, 4, axis=1)
        if self.with_peephole:
            p = params["peep"][:, None, :, None, None]  # (3,1,co,1,1)
            i = jax.nn.sigmoid(i + p[0] * c)
            f = jax.nn.sigmoid(f + p[1] * c)
        else:
            i, f = jax.nn.sigmoid(i), jax.nn.sigmoid(f)
        g = jnp.tanh(g)
        new_c = f * c + i * g
        o = jax.nn.sigmoid(o + (p[2] * new_c if self.with_peephole else 0.0))
        new_h = o * jnp.tanh(new_c)
        return (new_h, new_c), new_h


class Recurrent(Container):
    """Time-loop driver over a Cell via ``lax.scan`` (reference: Recurrent).

    Input (N, T, D) → output (N, T, H). ``add(cell)`` mirrors the reference's
    ``Recurrent().add(LSTM(...))`` wiring.
    """

    def __init__(self, cell: Optional[Cell] = None):
        super().__init__(*([cell] if cell is not None else []))

    def add(self, cell: Cell) -> "Recurrent":
        if len(self.modules) >= 1:
            raise ValueError("Recurrent holds exactly one Cell")
        if not isinstance(cell, Cell):
            raise TypeError(f"Recurrent needs a Cell, got {type(cell).__name__}")
        return super().add(cell)

    @property
    def cell(self) -> Cell:
        return self.modules[0]

    def build(self, rng, in_spec):
        # per-step spec: drop the time axis; works for (N,T,D) vector cells and
        # (N,T,C,H,W) convolutional cells alike
        step_spec = jax.ShapeDtypeStruct(
            (in_spec.shape[0],) + in_spec.shape[2:], in_spec.dtype
        )
        self.cell.build(rng, step_spec)
        self._built = True
        out_step = jax.eval_shape(
            lambda p, c, xt: self.cell.step(p, c, xt)[1],
            self.cell.get_parameters(),
            self.cell.init_carry(in_spec.shape[0]),
            step_spec,
        )
        return jax.ShapeDtypeStruct(
            (in_spec.shape[0], in_spec.shape[1]) + out_step.shape[1:], out_step.dtype
        )

    def _apply(self, params, state, x, training, rng):
        cell = self.cell
        cell_params = params[cell.name()]
        carry0 = cell.init_carry(x.shape[0])

        def body(carry, x_t):
            with child_scope(cell):
                new_carry, y = cell.step(cell_params, carry, x_t)
            return new_carry, y

        xs = jnp.swapaxes(x, 0, 1)  # (T, N, D) for scan
        _, ys = lax.scan(body, carry0, xs)
        return jnp.swapaxes(ys, 0, 1), {cell.name(): state[cell.name()]}


class BiRecurrent(Container):
    """Forward + time-reversed Recurrent with merged outputs (reference: BiRecurrent).

    ``merge_mode``: 'add' (reference default CAddTable) or 'concat' (JoinTable on
    the feature dim).
    """

    def __init__(self, cell_fwd: Cell, cell_bwd: Optional[Cell] = None, merge_mode: str = "add"):
        import copy

        if cell_bwd is None:
            # deep-copied cell keeps _name=None → each Recurrent wrapper assigns its
            # own deterministic child name, so checkpoint keys stay process-stable
            cell_bwd = copy.deepcopy(cell_fwd)
            cell_bwd._name = None
        if merge_mode not in ("add", "concat"):
            raise ValueError(f"unknown merge_mode {merge_mode!r}")
        super().__init__(Recurrent(cell_fwd), Recurrent(cell_bwd))
        self.merge_mode = merge_mode

    def build(self, rng, in_spec):
        s1 = self.modules[0].build(jax.random.fold_in(rng, 0), in_spec)
        self.modules[1].build(jax.random.fold_in(rng, 1), in_spec)
        self._built = True
        if self.merge_mode == "concat":
            return jax.ShapeDtypeStruct(
                s1.shape[:-1] + (2 * s1.shape[-1],), s1.dtype
            )
        return s1

    def _apply(self, params, state, x, training, rng):
        new_state = {}
        fwd = self._child_apply(self.modules[0], x, training, rng, params, state, new_state)
        rev_in = jnp.flip(x, axis=1)
        bwd = self._child_apply(self.modules[1], rev_in, training, rng, params, state, new_state)
        bwd = jnp.flip(bwd, axis=1)
        if self.merge_mode == "concat":
            return jnp.concatenate([fwd, bwd], axis=-1), new_state
        return fwd + bwd, new_state


class TimeDistributed(Container):
    """Apply a module independently per time step (reference: TimeDistributed).

    Implemented by folding time into the batch dim — one big batched op instead
    of T small ones (the reference loops).
    """

    def __init__(self, module: AbstractModule):
        super().__init__(module)

    def build(self, rng, in_spec):
        inner_spec = jax.ShapeDtypeStruct(
            (in_spec.shape[0] * in_spec.shape[1],) + in_spec.shape[2:], in_spec.dtype
        )
        out = self.modules[0].build(rng, inner_spec)
        self._built = True
        return jax.ShapeDtypeStruct(
            (in_spec.shape[0], in_spec.shape[1]) + out.shape[1:], out.dtype
        )

    def _apply(self, params, state, x, training, rng):
        n, t = x.shape[0], x.shape[1]
        flat = x.reshape((n * t,) + x.shape[2:])
        new_state = {}
        y = self._child_apply(self.modules[0], flat, training, rng, params, state, new_state)
        return y.reshape((n, t) + y.shape[1:]), new_state


class RecurrentDecoder(Container):
    """Feed each output back as the next input for ``seq_length`` steps
    (reference: RecurrentDecoder). Input: (N, D) start token."""

    def __init__(self, seq_length: int, cell: Optional[Cell] = None):
        super().__init__(*([cell] if cell is not None else []))
        self.seq_length = seq_length

    def add(self, cell: Cell) -> "RecurrentDecoder":
        return Container.add(self, cell)

    @property
    def cell(self) -> Cell:
        return self.modules[0]

    def build(self, rng, in_spec):
        self.cell.build(rng, in_spec)
        self._built = True
        return jax.ShapeDtypeStruct(
            (in_spec.shape[0], self.seq_length, self.cell.hidden_size), in_spec.dtype
        )

    def _apply(self, params, state, x, training, rng):
        cell = self.cell
        cell_params = params[cell.name()]
        carry0 = cell.init_carry(x.shape[0])

        def body(carry_and_x, _):
            carry, x_t = carry_and_x
            with child_scope(cell):
                new_carry, y = cell.step(cell_params, carry, x_t)
            return (new_carry, y), y

        _, ys = lax.scan(body, (carry0, x), None, length=self.seq_length)
        return jnp.swapaxes(ys, 0, 1), {cell.name(): state[cell.name()]}
