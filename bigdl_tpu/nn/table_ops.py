"""Multi-branch containers and Table (pytree) ops (reference: ``$DL/nn/Concat.scala``,
``ConcatTable.scala``, ``ParallelTable.scala``, ``JoinTable.scala``, ``CAddTable.scala``,
``SelectTable.scala``, ``MixtureTable.scala``...).

``Concat`` is Inception's workhorse: the reference hand-threads a multi-core copy
into a preallocated output; here it is one ``jnp.concatenate`` that XLA schedules.
Dims are 1-based (Torch convention) throughout.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp

from ..utils.table import T, Table
from .module import AbstractModule, Container, run_child


def _as_list(x) -> List[Any]:
    if isinstance(x, Table):
        return x.to_list()
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def check_concat_specs(module, specs, axis: int, names) -> None:
    """Merge-point contract check: every branch must agree on rank and on all
    non-concat dims; reports the first offending pair with both shapes."""
    ref = tuple(specs[0].shape)
    if not 0 <= axis < len(ref):
        raise ValueError(
            f"{module.name()}: concat dim {axis + 1} (1-based) out of range "
            f"for rank-{len(ref)} inputs (first branch shape {ref})"
        )
    for name, s in zip(names[1:], specs[1:]):
        cur = tuple(s.shape)
        if len(cur) != len(ref) or any(
            i != axis and a != b for i, (a, b) in enumerate(zip(ref, cur))
        ):
            raise ValueError(
                f"{module.name()}: cannot concatenate along dim {axis + 1} "
                f"(1-based): {names[0]} outputs {ref} but {name} outputs {cur}"
            )


class Concat(Container):
    """Apply each branch to the SAME input, concat outputs along dim (1-based).

    Reference: $DL/nn/Concat.scala.
    """

    def __init__(self, dimension: int = 2):
        super().__init__()
        self.dimension = dimension

    def infer_shape(self, in_spec):
        from .module import infer_module_shape

        specs = [infer_module_shape(m, in_spec) for m in self.modules]
        d = self.dimension - 1
        check_concat_specs(self, specs, d, [m.name() for m in self.modules])
        shape = list(specs[0].shape)
        shape[d] = sum(s.shape[d] for s in specs)
        return jax.ShapeDtypeStruct(
            tuple(shape), jnp.result_type(*[s.dtype for s in specs])
        )

    def build(self, rng, in_spec):
        specs = [m.build(jax.random.fold_in(rng, i), in_spec) for i, m in enumerate(self.modules)]
        self._built = True
        return jax.eval_shape(
            lambda *ys: jnp.concatenate(ys, axis=self.dimension - 1), *specs
        )

    def _apply(self, params, state, x, training, rng):
        new_state: Dict[str, Any] = {}
        ys = [
            self._child_apply(m, x, training, rng, params, state, new_state)
            for m in self.modules
        ]
        return jnp.concatenate(ys, axis=self.dimension - 1), new_state


class ConcatTable(Container):
    """Apply each branch to the same input; output a Table of results
    (reference: ConcatTable)."""

    def infer_shape(self, in_spec):
        from .module import infer_module_shape

        return T(*[infer_module_shape(m, in_spec) for m in self.modules])

    def build(self, rng, in_spec):
        specs = [m.build(jax.random.fold_in(rng, i), in_spec) for i, m in enumerate(self.modules)]
        self._built = True
        return T(*specs)

    def _apply(self, params, state, x, training, rng):
        new_state: Dict[str, Any] = {}
        ys = [
            self._child_apply(m, x, training, rng, params, state, new_state)
            for m in self.modules
        ]
        return T(*ys), new_state


class ParallelTable(Container):
    """i-th module applied to i-th input (reference: ParallelTable)."""

    accepts_table_input = True

    def infer_shape(self, in_spec):
        from .module import infer_module_shape

        specs = _as_list(in_spec)
        if len(specs) != len(self.modules):
            raise ValueError(
                f"{self.name()}: {len(self.modules)} branches but "
                f"{len(specs)} inputs"
            )
        return T(*[
            infer_module_shape(m, s) for m, s in zip(self.modules, specs)
        ])

    def build(self, rng, in_spec):
        specs = _as_list(in_spec)
        outs = [
            m.build(jax.random.fold_in(rng, i), s)
            for i, (m, s) in enumerate(zip(self.modules, specs))
        ]
        self._built = True
        return T(*outs)

    def _apply(self, params, state, x, training, rng):
        xs = _as_list(x)
        new_state: Dict[str, Any] = {}
        ys = [
            self._child_apply(m, xi, training, rng, params, state, new_state)
            for m, xi in zip(self.modules, xs)
        ]
        return T(*ys), new_state


class MapTable(Container):
    """One shared module applied to every input entry (reference: MapTable).

    Weight sharing is real: the single child's params are used for all entries.
    """

    def __init__(self, module: AbstractModule):
        super().__init__(module)

    accepts_table_input = True

    def infer_shape(self, in_spec):
        from .module import infer_module_shape

        specs = _as_list(in_spec)
        return T(*[infer_module_shape(self.modules[0], s) for s in specs])

    def build(self, rng, in_spec):
        specs = _as_list(in_spec)
        out0 = self.modules[0].build(rng, specs[0])
        self._built = True
        return T(*([out0] * len(specs)))

    def _apply(self, params, state, x, training, rng):
        xs = _as_list(x)
        m = self.modules[0]
        # thread the shared child's state sequentially through the entries so
        # updates (e.g. BN running stats) from every entry are kept
        s = state[m.name()]
        ys = []
        for xi in xs:
            y, s = run_child(m, params[m.name()], s, xi, training, rng)
            ys.append(y)
        return T(*ys), {m.name(): s}


class JoinTable(AbstractModule):
    """Concatenate a Table of tensors along dim (1-based; n_input_dims enables
    batch-relative dims) — reference: JoinTable."""

    accepts_table_input = True

    def __init__(self, dimension: int, n_input_dims: int = 0):
        super().__init__()
        self.dimension = dimension
        self.n_input_dims = n_input_dims

    def infer_shape(self, in_spec):
        xs = _as_list(in_spec)
        if not xs:
            raise ValueError(f"{self.name()}: empty input Table")
        d = self.dimension - 1
        if self.n_input_dims > 0 and len(xs[0].shape) > self.n_input_dims:
            d += 1
        check_concat_specs(
            self, xs, d, [f"table entry {i + 1}" for i in range(len(xs))]
        )
        return self._infer_shape_via_apply(in_spec)

    def _apply(self, params, state, x, training, rng):
        xs = _as_list(x)
        d = self.dimension - 1
        if self.n_input_dims > 0 and xs[0].ndim > self.n_input_dims:
            d += 1  # batched input: dim counts exclude the batch dim
        return jnp.concatenate(xs, axis=d), state


class _ElementwiseTable(AbstractModule):
    accepts_table_input = True

    def infer_shape(self, in_spec):
        xs = _as_list(in_spec)
        if not xs:
            raise ValueError(f"{self.name()}: empty input Table")
        shape = tuple(xs[0].shape)
        for i, s in enumerate(xs[1:], 2):
            try:
                shape = jnp.broadcast_shapes(shape, tuple(s.shape))
            except ValueError:
                raise ValueError(
                    f"{self.name()}: table entry 1 shape {tuple(xs[0].shape)} "
                    f"does not broadcast with entry {i} shape {tuple(s.shape)}"
                ) from None
        return self._infer_shape_via_apply(in_spec)

    def _combine(self, a, b):
        raise NotImplementedError

    def _apply(self, params, state, x, training, rng):
        xs = _as_list(x)
        out = xs[0]
        for xi in xs[1:]:
            out = self._combine(out, xi)
        return out, state


class CAddTable(_ElementwiseTable):
    """Elementwise sum of a Table (reference: CAddTable) — ResNet's shortcut add."""

    def __init__(self, inplace: bool = False):
        super().__init__()

    def _combine(self, a, b):
        return a + b


class CSubTable(_ElementwiseTable):
    def _combine(self, a, b):
        return a - b


class CMulTable(_ElementwiseTable):
    def _combine(self, a, b):
        return a * b


class CDivTable(_ElementwiseTable):
    def _combine(self, a, b):
        return a / b


class CMaxTable(_ElementwiseTable):
    def _combine(self, a, b):
        return jnp.maximum(a, b)


class CMinTable(_ElementwiseTable):
    def _combine(self, a, b):
        return jnp.minimum(a, b)


class CAveTable(AbstractModule):
    accepts_table_input = True
    infer_shape = AbstractModule._infer_shape_via_apply  # parameter-less

    def _apply(self, params, state, x, training, rng):
        xs = _as_list(x)
        return sum(xs) / len(xs), state


class SelectTable(AbstractModule):
    """Pick the i-th (1-based) entry of a Table (reference: SelectTable)."""

    accepts_table_input = True
    infer_shape = AbstractModule._infer_shape_via_apply  # parameter-less

    def __init__(self, index: int):
        super().__init__()
        self.index = index

    def _apply(self, params, state, x, training, rng):
        xs = _as_list(x)
        i = self.index - 1 if self.index > 0 else len(xs) + self.index
        return xs[i], state


class FlattenTable(AbstractModule):
    """Flatten nested Tables into one flat Table (reference: FlattenTable)."""

    accepts_table_input = True
    infer_shape = AbstractModule._infer_shape_via_apply  # parameter-less

    def _apply(self, params, state, x, training, rng):
        out: List[Any] = []

        def rec(v):
            if isinstance(v, Table) or isinstance(v, (list, tuple)):
                for e in _as_list(v):
                    rec(e)
            else:
                out.append(v)

        rec(x)
        return T(*out), state


class MixtureTable(AbstractModule):
    """Mixture-of-experts blend: input Table(gater (N,E), experts Table)
    (reference: MixtureTable)."""

    accepts_table_input = True
    infer_shape = AbstractModule._infer_shape_via_apply  # parameter-less

    def _apply(self, params, state, x, training, rng):
        gater, experts = _as_list(x)[:2]
        es = _as_list(experts)
        stacked = jnp.stack(es, axis=1)  # (N, E, ...)
        g = gater.reshape(gater.shape + (1,) * (stacked.ndim - 2))
        return jnp.sum(stacked * g, axis=1), state


class DotProduct(AbstractModule):
    """Row-wise dot product of Table(a, b) (reference: DotProduct)."""

    accepts_table_input = True
    infer_shape = AbstractModule._infer_shape_via_apply  # parameter-less

    def _apply(self, params, state, x, training, rng):
        a, b = _as_list(x)[:2]
        return jnp.sum(a * b, axis=-1), state


class CosineDistance(AbstractModule):
    """Row-wise cosine similarity of Table(a, b) (reference: CosineDistance)."""

    accepts_table_input = True
    infer_shape = AbstractModule._infer_shape_via_apply  # parameter-less

    def _apply(self, params, state, x, training, rng):
        a, b = _as_list(x)[:2]
        num = jnp.sum(a * b, axis=-1)
        den = jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1)
        return num / jnp.clip(den, 1e-12), state


class PairwiseDistance(AbstractModule):
    """Row-wise Lp distance of Table(a, b) (reference: PairwiseDistance)."""

    accepts_table_input = True
    infer_shape = AbstractModule._infer_shape_via_apply  # parameter-less

    def __init__(self, norm: int = 2):
        super().__init__()
        self.norm = norm

    def _apply(self, params, state, x, training, rng):
        a, b = _as_list(x)[:2]
        return jnp.sum(jnp.abs(a - b) ** self.norm, axis=-1) ** (1.0 / self.norm), state


class MM(AbstractModule):
    """Batch matrix multiply of Table(a, b) with optional transposes (reference: MM)."""

    accepts_table_input = True
    infer_shape = AbstractModule._infer_shape_via_apply  # parameter-less

    def __init__(self, trans_a: bool = False, trans_b: bool = False):
        super().__init__()
        self.trans_a, self.trans_b = trans_a, trans_b

    def _apply(self, params, state, x, training, rng):
        a, b = _as_list(x)[:2]
        if self.trans_a:
            a = jnp.swapaxes(a, -1, -2)
        if self.trans_b:
            b = jnp.swapaxes(b, -1, -2)
        return a @ b, state


class MV(AbstractModule):
    """Batch matrix-vector multiply of Table(mat, vec) (reference: MV)."""

    accepts_table_input = True
    infer_shape = AbstractModule._infer_shape_via_apply  # parameter-less

    def __init__(self, trans: bool = False):
        super().__init__()
        self.trans = trans

    def _apply(self, params, state, x, training, rng):
        m, v = _as_list(x)[:2]
        if self.trans:
            m = jnp.swapaxes(m, -1, -2)
        return jnp.einsum("...ij,...j->...i", m, v), state
