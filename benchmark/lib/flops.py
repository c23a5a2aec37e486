"""Operations and least bytes of a forward pass, from shapes alone.

Walks the jaxpr of a function and, for every ``conv_general_dilated`` and
``dot_general`` equation (sub-jaxprs included), counts

* FLOPs: 2 x output elements x contracted elements per output element;
* least bytes: both operands and the result, once each, in their own dtypes.

XLA's ``cost_analysis`` is not used: its ``bytes_accessed`` counts what the
compiled program touches, not what the algorithm needs.
"""

from __future__ import annotations

import math
from typing import List, NamedTuple

import jax

MATMUL_PRIMITIVES = ("conv_general_dilated", "dot_general")


class OpCost(NamedTuple):
    primitive: str
    flops: float
    bytes: float


def _nbytes(aval) -> int:
    return math.prod(aval.shape) * aval.dtype.itemsize


def _eqn_cost(eqn) -> OpCost:
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    out = eqn.outvars[0].aval
    if eqn.primitive.name == "dot_general":
        (contract_lhs, _), _ = eqn.params["dimension_numbers"]
        contracted = math.prod(lhs.shape[d] for d in contract_lhs)
    else:
        # each output element sums over the kernel's spatial extent times
        # the input features of its group: all of rhs but its output-feature
        # dimension
        dn = eqn.params["dimension_numbers"]
        contracted = math.prod(rhs.shape) // rhs.shape[dn.rhs_spec[0]]
    flops = 2.0 * math.prod(out.shape) * contracted
    return OpCost(eqn.primitive.name, flops,
                  float(_nbytes(lhs) + _nbytes(rhs) + _nbytes(out)))


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(item, "eqns"):
                yield item
            elif hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr


def _walk(jaxpr, out: List[OpCost]) -> None:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in MATMUL_PRIMITIVES:
            out.append(_eqn_cost(eqn))
        for sub in _sub_jaxprs(eqn):
            _walk(sub, out)


def matmul_costs(fn, *args) -> List[OpCost]:
    """One ``OpCost`` per convolution and matrix product that ``fn(*args)``
    runs; ``args`` may be ``jax.ShapeDtypeStruct``s."""
    out: List[OpCost] = []
    _walk(jax.make_jaxpr(fn)(*args).jaxpr, out)
    return out


def least_seconds(costs, peak_flops_per_s: float, peak_bytes_per_s: float):
    """(least time, which bound) for the ops together: the larger of all
    their FLOPs over the peak and all their least bytes over the bandwidth."""
    compute = sum(c.flops for c in costs) / peak_flops_per_s
    memory = sum(c.bytes for c in costs) / peak_bytes_per_s
    return max(compute, memory), ("compute" if compute >= memory else "memory")
