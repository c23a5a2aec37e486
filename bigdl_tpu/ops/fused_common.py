"""Shared plumbing for the fused elementwise/normalization kernels.

The fused kernels (``fused_norm``, ``fused_epilogue``) all view their operand
as a 2-D (rows, features) matrix and tile over row blocks; this module owns
the row-block geometry, the zero-pad-to-block trick that keeps the kernels
mask-free (a zero pad row contributes exactly zero to every reduction the
backward kernels accumulate).

Gate semantics: kernels engage under ``Engine.set_fused_kernels(True)`` (or
``BIGDL_FUSED_KERNELS=1``) — the call sites read ``Engine.fused_kernels()``
and nothing else. On the TPU they compile through Mosaic (a compile failure
is the compiler's own error, never a silent reroute to XLA); off the TPU they
run in interpret mode through ``utils.compat.pallas_call``, so tier-1
exercises the REAL kernel programs under ``JAX_PLATFORMS=cpu``. Read at TRACE
time, like every other Engine policy: flip the switch before building/jitting.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def block_rows(n_rows: int, row_bytes: int, itemsize: int,
               live_factor: int = 8) -> int:
    """Row-block size for a (rows, features) kernel: the largest whole number
    of sublane tiles whose working set (``live_factor`` live row-block-sized
    values — inputs, f32 upcasts, intermediates, outputs) stays within a
    ~4 MB slice of the 16 MB scoped-VMEM budget.

    ``itemsize`` is the narrowest dtype among the row-blocked operands: a
    (sublane, 128) tile packs 8 rows of 32-bit values, 16 of bf16, 32 of
    8-bit, and Mosaic wants the block's row count to be whole tiles. The
    result is never below one tile — ``pad_rows`` pads short inputs up to
    it — so every block the fused kernels launch is tile-aligned."""
    sublane = max(8, 32 // itemsize)
    budget = 4 << 20
    br = budget // max(1, row_bytes * live_factor)
    br = min(br, 1024, -(-n_rows // sublane) * sublane)
    return max(sublane, br - br % sublane)


def pad_rows(x2d: jax.Array, br: int) -> Tuple[jax.Array, int]:
    """Zero-pad the row dim up to a multiple of ``br``.

    Zero rows are inert through every fused kernel: forward pad rows are
    sliced back off, and backward reductions (dw/db accumulations) see zero
    cotangents for them — so no in-kernel row masking is needed, which keeps
    the tail block on the same fast path as the full blocks."""
    r = x2d.shape[0]
    pad = (-r) % br
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    return x2d, r
