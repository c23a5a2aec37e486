"""Max-pooling with a Pallas TPU backward kernel.

XLA derives the gradient of ``lax.reduce_window(max)`` as a SelectAndScatter
op: with the backward's ``reduce-window`` it is 13.6 of the 53.1 ms of
Inception-v1's train step on a v5e (13 max pools; ``PERF.md`` §5,
``select-and-scatter`` 9.9 + ``reduce-window`` 3.7). The reference hits the
same problem with a dedicated native kernel
(``$DL/nn/SpatialMaxPooling.scala`` backward loops in Scala/MKL); this is
the TPU-native equivalent.

Design — one fused backward kernel, HBM-minimal:
  traffic = read x + read dy + write dx (the information-theoretic floor;
  the windowed argmax is RECOMPUTED from x in VMEM instead of being saved
  as an activation, so forward stays XLA's reduce_window and no extra
  residual is stored).

Per (channel-slab, H, W) block, everything in VMEM/registers:
  1. pad x to the window-covered extent with -inf (handles torch pad
     semantics and ceil-mode windows that overhang the input),
  2. recompute the per-window max AND first-argmax by unrolling the
     kh*kw window offsets as strided slices (VPU shuffles — ties resolve
     to the first element in row-major window order, matching XLA's
     SelectAndScatter select-function semantics),
  3. route dy to argmax positions by accumulating, per window offset
     (a, b), the masked dy dilated by the stride and shifted by (a, b) —
     a scatter expressed as kh*kw dense adds, none of which leave VMEM.

Used by ``nn.SpatialMaxPooling`` (and everything built on it: the keras
wrapper, the TF/caffe importers, the zoo CNNs) through the ``maxpool2d``
custom-vjp below; non-TPU backends keep XLA's native gradient.
``interpret=True`` runs the kernel on CPU for the parity tests.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.compat import pallas_call

_NEG = float("-inf")


def _bwd_kernel(x_ref, dy_ref, dx_ref, acc_ref, *, kernel: Tuple[int, int],
                stride: Tuple[int, int], pad_lo: Tuple[int, int],
                out_hw: Tuple[int, int]):
    """See module docstring. Layout strategy: the residue decomposition
    needs strided access along both H (sublanes — cheap reshape-split) and
    W (lanes — no Mosaic support). For sw > 1 the whole middle section
    therefore runs in TRANSPOSED (.., W, H) coordinates: one minor-dims
    transpose per H-residue row on the way in (+1 for dy), one per row on
    the way out, and every other op is a plain slice/compare/add. That is
    2*sh + 1 transposes total instead of transposing every plane in both
    directions; for sw == 1 (the stride-1 pools) there are none at all.
    """
    kh, kw = kernel
    sh, sw = stride
    ph, pw = pad_lo
    ho, wo = out_hw
    x = x_ref[...]
    dy = dy_ref[...]
    bc, h, w = x.shape
    # window-covered extent (may overhang the padded input in ceil mode),
    # rounded up to stride multiples for the residue decomposition
    hp, wp = (ho - 1) * sh + kh, (wo - 1) * sw + kw
    th, tw = -(-hp // sh), -(-wp // sw)
    hp2, wp2 = th * sh, tw * sw
    # floor mode can leave trailing input rows outside every window: drop them
    xq = x[:, :min(h, hp2 - ph), :min(w, wp2 - pw)]
    xp = lax.pad(xq, jnp.array(_NEG, x.dtype),
                 ((0, 0, 0), (ph, hp2 - ph - xq.shape[1], 0),
                  (pw, wp2 - pw - xq.shape[2], 0)))
    flip = sw > 1  # transposed-coordinate mode

    # residue planes: plane[r][s][t, u] = xp[sh*t + r, sw*u + s]
    # (stored as (bc, tw, th) when flip — W becomes the sublane dim)
    planes = []
    for r in range(sh):
        row = xp.reshape(bc, th, sh, wp2)[:, :, r, :] if sh > 1 else xp
        if flip:
            rt = jnp.swapaxes(row, 1, 2)  # (bc, wp2, th)
            planes.append([rt.reshape(bc, tw, sw, th)[:, :, s, :]
                           for s in range(sw)])
        else:
            planes.append([row])
    dyf = jnp.swapaxes(dy, 1, 2) if flip else dy

    # ---- recompute per-window max + FIRST argmax (row-major tie-break);
    # window offset (a, b) = plane[a%sh][b%sw] shifted by (a//sh, b//sw) ----
    best = None
    idx = None
    for a in range(kh):
        for b in range(kw):
            p = planes[a % sh][b % sw]
            da, db = a // sh, b // sw
            lo = (0, db, da) if flip else (0, da, db)
            hi = (bc, db + wo, da + ho) if flip else (bc, da + ho, db + wo)
            v = lax.slice(p, lo, hi)
            if best is None:
                best = v
                idx = jnp.zeros(v.shape, jnp.int32)
                continue
            take = v > best  # strict: earlier offsets win ties
            idx = jnp.where(take, jnp.int32(a * kw + b), idx)
            best = jnp.where(take, v, best)

    # ---- scatter dy to argmax positions, accumulated per residue plane.
    # The shifted adds go through a VMEM scratch ref with static-slice
    # stores: expressing the (da, db) shift as lax.pad trips a Mosaic
    # layout bug (offset mismatch on the pad's internal concat) ----
    zero = jnp.array(0, x.dtype)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for a in range(kh):
        for b in range(kw):
            m = jnp.where(idx == a * kw + b, dyf, zero)
            da, db = a // sh, b // sw
            plane = a % sh * sw + b % sw
            if flip:
                acc_ref[plane, :, db:db + wo, da:da + ho] = (
                    acc_ref[plane, :, db:db + wo, da:da + ho] + m)
            else:
                acc_ref[plane, :, da:da + ho, db:db + wo] = (
                    acc_ref[plane, :, da:da + ho, db:db + wo] + m)

    # reassemble: W-interleave is a cheap sublane stack in flipped coords,
    # then one transpose per H-residue row, then the H sublane interleave
    rows = []
    for r in range(sh):
        if flip:
            mr = jnp.stack([acc_ref[r * sw + s] for s in range(sw)],
                           axis=2).reshape(bc, wp2, th)
            rows.append(jnp.swapaxes(mr, 1, 2))
        else:
            rows.append(acc_ref[r * sw])
    dxp = (jnp.stack(rows, axis=2).reshape(bc, hp2, wp2)
           if sh > 1 else rows[0])
    # zero-fill any input rows no window touched, then cut the user's view
    dxp = lax.pad(dxp, zero,
                  ((0, 0, 0), (0, max(0, ph + h - hp2), 0),
                   (0, max(0, pw + w - wp2), 0)))
    dx_ref[...] = lax.slice(dxp, (0, ph, pw), (bc, ph + h, pw + w))


def _block_channels(nc: int, h: int, w: int, ho: int, wo: int,
                    itemsize: int) -> int:
    """Largest channel-slab count fitting the kernel's VMEM working set.

    Besides x+dx blocks, the kernel keeps ~10 live slab-sized values
    (padded input, residue planes, window shifts, best/idx, scratch
    accumulators) — budget ~2 MB of block-IO against the 16 MB scoped
    limit, empirically leaving room for the intermediates.
    """
    lanes = 128
    slab = (2 * h * pl.cdiv(w, lanes) + 3 * ho * pl.cdiv(wo, lanes)) \
        * lanes * itemsize
    bc = max(1, (2 << 20) // max(slab, 1))
    bc = min(nc, bc)
    if bc >= 8:
        bc -= bc % 8
    return bc


def _maxpool_grad_nchw(x, dy, kernel, stride, pad_lo, out_hw,
                       interpret=False):
    n, c, h, w = x.shape
    ho, wo = out_hw
    nc = n * c
    xf = x.reshape(nc, h, w)
    dyf = dy.reshape(nc, ho, wo)
    bc = _block_channels(nc, h, w, ho, wo, x.dtype.itemsize)
    grid = (pl.cdiv(nc, bc),)
    kh, kw = kernel
    sh, sw = stride
    th = -(-((ho - 1) * sh + kh) // sh)
    tw = -(-((wo - 1) * sw + kw) // sw)
    # accumulator planes live in flipped (W, H) coords when sw > 1
    plane_hw = (tw, th) if sw > 1 else (th, tw)
    dx = pallas_call(
        functools.partial(_bwd_kernel, kernel=kernel, stride=stride,
                          pad_lo=pad_lo, out_hw=out_hw),
        grid=grid,
        in_specs=[pl.BlockSpec((bc, h, w), lambda i: (i, 0, 0)),
                  pl.BlockSpec((bc, ho, wo), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((bc, h, w), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nc, h, w), x.dtype),
        scratch_shapes=[pltpu.VMEM((sh * sw, bc) + plane_hw, x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(xf, dyf)
    return dx.reshape(n, c, h, w)


def _use_pallas_grad() -> bool:
    """Kernel gate — OPT-IN (``BIGDL_MAXPOOL_GRAD_IMPL=pallas``), TPU only.

    The last on-chip measurement (builder, round 4: in-jit repetition,
    resnet-stem 112→56 3x3/s2p1 b128×64ch f32) had the kernel at 9.766 ms vs
    XLA SelectAndScatter's 4.379 ms (0.45×), and pure-copy probes at the same
    channel-slab blocking topped out at ~185 GB/s — below the 211 GB/s
    effective rate XLA's native op achieved on the same traffic. The
    transpose-count rewrite (12→5) landed after that measurement and has not
    been re-measured (ROADMAP Design D3 settles it). On jax 0.9.0 / libtpu
    0.0.34 the kernel compiles and matches SelectAndScatter at the stem pool
    and at Inception's 7×7 and 28×28 pools (chip run, PR 21) — the two
    shapes Mosaic refused under the round-5 libtpu — at 20–50 s of Mosaic
    compile per geometry."""
    return jax.default_backend() == "tpu" and _grad_impl() == "pallas"


def _reduce_window_max(x, kernel, stride, padding):
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        window_dimensions=(1, 1) + tuple(kernel),
        window_strides=(1, 1) + tuple(stride),
        padding=((0, 0), (0, 0)) + tuple(padding),
    ).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def maxpool2d(x, kernel: Tuple[int, int], stride: Tuple[int, int],
              padding: Tuple[Tuple[int, int], Tuple[int, int]]):
    """NCHW max pool; forward is XLA's reduce_window, backward the Pallas
    kernel on TPU (XLA's SelectAndScatter elsewhere).

    ``padding`` is ((ph_lo, ph_hi), (pw_lo, pw_hi)) — already resolved by
    the caller (torch floor/ceil/SAME rules live in ``nn.pooling``).
    """
    return _reduce_window_max(x, kernel, stride, padding)


def _mp_fwd(x, kernel, stride, padding):
    return maxpool2d(x, kernel, stride, padding), x


def _grad_impl() -> str:
    """Backward implementation choice, resolved at trace time.

    ``BIGDL_MAXPOOL_GRAD_IMPL`` ∈ {sas (default: XLA SelectAndScatter),
    shift (pure-XLA strided-compare decomposition, ``maxpool_grad_shift``),
    pallas (the Mosaic kernel — also reachable via the legacy
    ``BIGDL_ENABLE_PALLAS_MAXPOOL_GRAD=1``)}. Both alternatives are
    opt-in pending the on-chip A/B through ``optimize()`` on
    ``inception_v1.hostfed`` (ROADMAP Design D3)."""
    impl = os.environ.get("BIGDL_MAXPOOL_GRAD_IMPL", "").lower()
    if impl == "xla":  # a second spelling of the SelectAndScatter side
        impl = "sas"
    if impl in ("sas", "shift", "pallas"):
        return impl
    if impl:
        # a typo here would silently mislabel an A/B measurement
        import warnings

        warnings.warn(
            f"BIGDL_MAXPOOL_GRAD_IMPL={impl!r} not recognized "
            "(expected sas|shift|pallas); using the default",
            RuntimeWarning, stacklevel=2)
    from ..utils.engine import env_flag

    return "pallas" if env_flag("BIGDL_ENABLE_PALLAS_MAXPOOL_GRAD") else "sas"


def _mp_bwd(kernel, stride, padding, x, dy):
    if _grad_impl() == "shift":
        return (maxpool_grad_shift(x, dy, tuple(kernel), tuple(stride),
                                   tuple(padding)),)
    if _use_pallas_grad():
        (ph_lo, _), (pw_lo, _) = padding
        return (_maxpool_grad_nchw(x, dy, tuple(kernel), tuple(stride),
                                   (ph_lo, pw_lo), tuple(dy.shape[2:])),)
    _, vjp = jax.vjp(
        lambda v: _reduce_window_max(v, kernel, stride, padding), x)
    return vjp(dy)


maxpool2d.defvjp(_mp_fwd, _mp_bwd)


def maxpool_grad_reference(x, dy, kernel, stride, padding):
    """XLA's own SelectAndScatter gradient — the parity oracle for tests."""
    _, vjp = jax.vjp(
        lambda v: _reduce_window_max(v, kernel, stride, padding), x)
    return vjp(dy)[0]


def maxpool_grad_shift(x, dy, kernel, stride, padding):
    """Pure-XLA maxpool backward as kh·kw strided compares + dilated pads —
    no SelectAndScatter, no Mosaic.

    Same decomposition as the Pallas kernel's step 3, expressed in HLO:
    for each in-window offset (a, b), the input positions it addresses are
    one strided slice of the padded input; their gradient contribution is
    ``dy * (x_slice == window_max)``, placed back by an interior-dilated
    pad (stride-1 interior, offset lo) — all elementwise/pad ops XLA
    fuses. Not measured against SelectAndScatter on the chip (``PERF.md``
    §5 has what the default costs; ROADMAP Design D3 has the A/B).

    Tie semantics differ from SelectAndScatter: gradient flows to EVERY
    tied max position in a window, not just the first in row-major order —
    a valid subgradient either way. This matters in practice: post-ReLU
    feature maps carry exact zeros, so all-zero windows tie (especially
    early in training) and whole-model gradients measurably differ from
    SAS while training equivalently (maxpool-CNN overfit drive converges
    identically; full-Inception grad check shows the expected tie-driven
    spread). Opt-in via BIGDL_MAXPOOL_GRAD_IMPL=shift pending an on-chip
    A/B.
    """
    n, c, h, w = x.shape
    kh, kw = kernel
    sh, sw = stride
    (ph_lo, _), (pw_lo, _) = padding
    ho, wo = dy.shape[2:]
    # padded working extent must cover BOTH the windowed span (for the
    # strided slices) and the full input span (for the final crop — with
    # stride > kernel or floor-mode the windows stop short of the input)
    hpad = max((ho - 1) * sh + kh, ph_lo + h)
    wpad = max((wo - 1) * sw + kw, pw_lo + w)
    x_pad = jnp.pad(x, ((0, 0), (0, 0),
                        (ph_lo, hpad - h - ph_lo),
                        (pw_lo, wpad - w - pw_lo)),
                    constant_values=_NEG)
    m = _reduce_window_max(x, kernel, stride, padding)
    dx_pad = jnp.zeros((n, c, hpad, wpad), dy.dtype)
    for a in range(kh):
        for b in range(kw):
            xs = lax.slice(x_pad, (0, 0, a, b),
                           (n, c, a + (ho - 1) * sh + 1,
                            b + (wo - 1) * sw + 1), (1, 1, sh, sw))
            contrib = jnp.where(xs == m, dy, jnp.zeros_like(dy))
            dx_pad = dx_pad + lax.pad(
                contrib, jnp.zeros((), dy.dtype),
                ((0, 0, 0), (0, 0, 0),
                 (a, hpad - a - ((ho - 1) * sh + 1), sh - 1),
                 (b, wpad - b - ((wo - 1) * sw + 1), sw - 1)))
    return lax.slice(dx_pad, (0, 0, ph_lo, pw_lo),
                     (n, c, ph_lo + h, pw_lo + w))
