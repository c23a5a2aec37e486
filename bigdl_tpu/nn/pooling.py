"""Pooling layers (reference: ``$DL/nn/SpatialMaxPooling.scala`` and siblings).

Torch semantics preserved: explicit (padW, padH), floor vs ceil output-size modes.
All lower to ``lax.reduce_window`` which XLA vectorizes on the VPU.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax.numpy as jnp
from jax import lax

from .module import AbstractModule


def _out_size(in_size: int, k: int, s: int, p: int, ceil_mode: bool) -> int:
    if ceil_mode:
        out = int(math.ceil((in_size + 2 * p - k) / s)) + 1
    else:
        out = int(math.floor((in_size + 2 * p - k) / s)) + 1
    if p > 0 and (out - 1) * s >= in_size + p:
        # Torch rule: last pooling window must start inside the input or left pad
        out -= 1
    return out


def _pool_padding(in_size: int, k: int, s: int, p: int, ceil_mode: bool) -> Tuple[int, int]:
    if p == -1:  # reference convention: pad = -1 means TF "SAME" (as in conv)
        out = int(math.ceil(in_size / s))
        total = max(0, (out - 1) * s + k - in_size)
        return total // 2, total - total // 2
    out = _out_size(in_size, k, s, p, ceil_mode)
    needed = max(0, (out - 1) * s + k - in_size - p)
    return p, needed


def _check_window(module, shape, spatial, kernel, pad=None) -> None:
    """Shared contract pre-check: every pooling window must fit the padded
    input; reports module name, geometry and both shapes on violation."""
    pads = pad if pad is not None else (0,) * len(kernel)
    for size, k, p in zip(spatial, kernel, pads):
        if p != -1 and size + 2 * p < k:
            raise ValueError(
                f"{module.name()}: pooling window {kernel} exceeds the padded "
                f"input extent (input shape {shape}, pad {pads})"
            )


class SpatialMaxPooling(AbstractModule):
    """Max pool over NCHW (reference: $DL/nn/SpatialMaxPooling.scala)."""

    def __init__(
        self,
        kernel_w: int,
        kernel_h: Optional[int] = None,
        stride_w: Optional[int] = None,
        stride_h: Optional[int] = None,
        pad_w: int = 0,
        pad_h: Optional[int] = None,
    ):
        super().__init__()
        kh = kernel_h if kernel_h is not None else kernel_w
        sw = stride_w if stride_w is not None else kernel_w
        sh = stride_h if stride_h is not None else kh
        self.kernel = (kh, kernel_w)
        self.stride = (sh, sw)
        self.pad = (pad_h if pad_h is not None else pad_w, pad_w)
        self.ceil_mode = False

    def ceil(self) -> "SpatialMaxPooling":
        self.ceil_mode = True
        return self

    def floor(self) -> "SpatialMaxPooling":
        self.ceil_mode = False
        return self

    def infer_shape(self, in_spec):
        shape = tuple(in_spec.shape)
        if len(shape) != 4:
            raise ValueError(f"{self.name()}: expects NCHW input, got shape {shape}")
        _check_window(self, shape, shape[2:], self.kernel, self.pad)
        return self._infer_shape_via_apply(in_spec)

    def _apply(self, params, state, x, training, rng):
        from ..ops.maxpool import maxpool2d

        (kh, kw), (sh, sw), (ph, pw) = self.kernel, self.stride, self.pad
        pad_h = _pool_padding(x.shape[2], kh, sh, ph, self.ceil_mode)
        pad_w = _pool_padding(x.shape[3], kw, sw, pw, self.ceil_mode)
        # forward = XLA reduce_window; backward = XLA's SelectAndScatter
        # unless BIGDL_MAXPOOL_GRAD_IMPL opts into another (ops/maxpool.py
        # _grad_impl; ROADMAP Design D3 has the A/B that decides)
        return maxpool2d(x, (kh, kw), (sh, sw), (pad_h, pad_w)), state


class SpatialAveragePooling(AbstractModule):
    """Average pool (reference: $DL/nn/SpatialAveragePooling.scala).

    ``count_include_pad`` mirrors the reference's countIncludePad (default True);
    ``global_pooling`` pools the full spatial extent regardless of kernel size.
    """

    def __init__(
        self,
        kernel_w: int,
        kernel_h: Optional[int] = None,
        stride_w: Optional[int] = None,
        stride_h: Optional[int] = None,
        pad_w: int = 0,
        pad_h: Optional[int] = None,
        global_pooling: bool = False,
        ceil_mode: bool = False,
        count_include_pad: bool = True,
        divide: bool = True,
    ):
        super().__init__()
        kh = kernel_h if kernel_h is not None else kernel_w
        sw = stride_w if stride_w is not None else kernel_w
        sh = stride_h if stride_h is not None else kh
        self.kernel = (kh, kernel_w)
        self.stride = (sh, sw)
        self.pad = (pad_h if pad_h is not None else pad_w, pad_w)
        self.global_pooling = global_pooling
        self.ceil_mode = ceil_mode
        self.count_include_pad = count_include_pad
        self.divide = divide

    def ceil(self) -> "SpatialAveragePooling":
        self.ceil_mode = True
        return self

    def infer_shape(self, in_spec):
        shape = tuple(in_spec.shape)
        if len(shape) != 4:
            raise ValueError(f"{self.name()}: expects NCHW input, got shape {shape}")
        if not self.global_pooling:
            _check_window(self, shape, shape[2:], self.kernel, self.pad)
        return self._infer_shape_via_apply(in_spec)

    def _apply(self, params, state, x, training, rng):
        if self.global_pooling:
            kh, kw = x.shape[2], x.shape[3]
            sh, sw, ph, pw = 1, 1, 0, 0
        else:
            (kh, kw), (sh, sw), (ph, pw) = self.kernel, self.stride, self.pad
        pad_h = _pool_padding(x.shape[2], kh, sh, ph, self.ceil_mode)
        pad_w = _pool_padding(x.shape[3], kw, sw, pw, self.ceil_mode)
        window = (1, 1, kh, kw)
        strides = (1, 1, sh, sw)
        padding = [(0, 0), (0, 0), pad_h, pad_w]
        summed = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
        if not self.divide:
            return summed, state
        # Torch divisor rule: divisor = window size clamped to the (input + explicit
        # pad) extent — pad cells count when count_include_pad, the ceil-mode
        # overhang never counts. Computed by reduce-summing a 0/1 eligibility mask
        # laid out over the exact realized extent of `summed`'s padded input.
        def count_mask(in_size, realized, p, include_pad):
            left, right = realized
            total = in_size + left + right
            i = jnp.arange(total)
            if not include_pad:
                m = (i >= left) & (i < left + in_size)
            elif p == -1:  # SAME: all realized pad cells are "explicit"
                m = i < total
            else:
                m = i < in_size + 2 * p
            return m.astype(x.dtype)

        mh = count_mask(x.shape[2], pad_h, ph, self.count_include_pad)
        mw = count_mask(x.shape[3], pad_w, pw, self.count_include_pad)
        counts = lax.reduce_window(
            mh[:, None] * mw[None, :], 0.0, lax.add, (kh, kw), (sh, sw), [(0, 0), (0, 0)]
        )
        return summed / jnp.maximum(counts, 1.0)[None, None], state


class VolumetricMaxPooling(AbstractModule):
    """3-D max pool over NCDHW (reference: $DL/nn/VolumetricMaxPooling.scala)."""

    def __init__(self, k_t: int, k_w: int, k_h: int, d_t: int = 1, d_w: int = 1, d_h: int = 1,
                 pad_t: int = 0, pad_w: int = 0, pad_h: int = 0):
        super().__init__()
        self.kernel = (k_t, k_h, k_w)
        self.stride = (d_t, d_h, d_w)
        self.pad = (pad_t, pad_h, pad_w)

    def infer_shape(self, in_spec):
        shape = tuple(in_spec.shape)
        if len(shape) != 5:
            raise ValueError(f"{self.name()}: expects NCDHW input, got shape {shape}")
        _check_window(self, shape, shape[2:], self.kernel, self.pad)
        return self._infer_shape_via_apply(in_spec)

    def _apply(self, params, state, x, training, rng):
        kt, kh, kw = self.kernel
        st, sh, sw = self.stride
        pt, ph, pw = self.pad
        y = lax.reduce_window(
            x,
            -jnp.inf,
            lax.max,
            window_dimensions=(1, 1, kt, kh, kw),
            window_strides=(1, 1, st, sh, sw),
            padding=[(0, 0), (0, 0), (pt, pt), (ph, ph), (pw, pw)],
        )
        return y.astype(x.dtype), state


class TemporalMaxPooling(AbstractModule):
    """1-D max pool over (N, T, C) (reference: $DL/nn/TemporalMaxPooling.scala)."""

    def __init__(self, k_w: int, d_w: Optional[int] = None):
        super().__init__()
        self.k_w = k_w
        self.d_w = d_w if d_w is not None else k_w

    def infer_shape(self, in_spec):
        shape = tuple(in_spec.shape)
        if len(shape) != 3:
            raise ValueError(f"{self.name()}: expects (N, T, C) input, got shape {shape}")
        _check_window(self, shape, (shape[1],), (self.k_w,))
        return self._infer_shape_via_apply(in_spec)

    def _apply(self, params, state, x, training, rng):
        y = lax.reduce_window(
            x,
            -jnp.inf,
            lax.max,
            window_dimensions=(1, self.k_w, 1),
            window_strides=(1, self.d_w, 1),
            padding="VALID",
        )
        return y.astype(x.dtype), state


class SpatialAdaptiveMaxPooling(AbstractModule):
    """Adaptive max pool to a fixed output size (reference file same name).

    Torch semantics: window i spans [floor(i*in/out), ceil((i+1)*in/out)).
    Implemented as a static unrolled slice/max per output cell (out sizes are small,
    e.g. 1..7; trace-friendly because all indices are static).
    """

    def __init__(self, out_w: int, out_h: int):
        super().__init__()
        self.out_w, self.out_h = out_w, out_h

    def infer_shape(self, in_spec):
        shape = tuple(in_spec.shape)
        if len(shape) != 4:
            raise ValueError(f"{self.name()}: expects NCHW input, got shape {shape}")
        return self._infer_shape_via_apply(in_spec)

    def _apply(self, params, state, x, training, rng):
        in_h, in_w = x.shape[2], x.shape[3]
        rows = []
        for i in range(self.out_h):
            h0, h1 = (i * in_h) // self.out_h, -(-((i + 1) * in_h) // self.out_h)
            cols = []
            for j in range(self.out_w):
                w0, w1 = (j * in_w) // self.out_w, -(-((j + 1) * in_w) // self.out_w)
                cols.append(jnp.max(x[:, :, h0:h1, w0:w1], axis=(2, 3)))
            rows.append(jnp.stack(cols, axis=-1))
        return jnp.stack(rows, axis=-2), state


class RoiPooling(AbstractModule):
    """Region-of-interest max pooling (reference: ``$DL/nn/RoiPooling.scala``).

    Input: Table(features (N, C, H, W), rois (R, 5) rows [batch_idx, x1, y1,
    x2, y2] in input-image coordinates). Output: (R, C, pooled_h, pooled_w).

    TPU-native design: instead of the reference's per-roi C++ loops, each
    output bin's max is computed with a broadcast row/col membership mask over
    the full feature map — one fused masked-max reduction per call, all static
    shapes (bin boundaries are traced arithmetic, not Python control flow).
    """

    accepts_table_input = True  # consumes a multi-parent Table when graph-wired

    def __init__(self, pooled_w: int, pooled_h: int, spatial_scale: float = 1.0):
        super().__init__()
        self.pooled_w = pooled_w
        self.pooled_h = pooled_h
        self.spatial_scale = spatial_scale

    def infer_shape(self, in_spec):
        import jax

        specs = list(in_spec) if not hasattr(in_spec, "shape") else [in_spec]
        if len(specs) < 2:
            raise ValueError(
                f"{self.name()}: expects Table(features NCHW, rois (R, 5)), "
                f"got {len(specs)} input(s)"
            )
        feats, rois = specs[0], specs[1]
        if len(feats.shape) != 4 or len(rois.shape) != 2 or rois.shape[1] != 5:
            raise ValueError(
                f"{self.name()}: expects Table(features NCHW, rois (R, 5)), got "
                f"shapes {tuple(feats.shape)} and {tuple(rois.shape)}"
            )
        return self._infer_shape_via_apply(in_spec)

    def _apply(self, params, state, x, training, rng):
        from ..utils.table import Table

        feats, rois = (x.to_list() if isinstance(x, Table) else list(x))[:2]
        n, c, h, w = feats.shape
        ph, pw = self.pooled_h, self.pooled_w
        batch_idx = rois[:, 0].astype(jnp.int32)
        # roi corners on the feature map (inclusive), Torch rounding
        x1 = jnp.round(rois[:, 1] * self.spatial_scale)
        y1 = jnp.round(rois[:, 2] * self.spatial_scale)
        x2 = jnp.round(rois[:, 3] * self.spatial_scale)
        y2 = jnp.round(rois[:, 4] * self.spatial_scale)
        roi_h = jnp.maximum(y2 - y1 + 1.0, 1.0)
        roi_w = jnp.maximum(x2 - x1 + 1.0, 1.0)
        bin_h = roi_h / ph  # (R,)
        bin_w = roi_w / pw

        def bounds(start, bin_size, n_bins, limit):
            i = jnp.arange(n_bins, dtype=jnp.float32)
            lo = jnp.floor(start[:, None] + i[None, :] * bin_size[:, None])
            hi = jnp.ceil(start[:, None] + (i[None, :] + 1.0) * bin_size[:, None])
            return (jnp.clip(lo, 0, limit), jnp.clip(hi, 0, limit))

        ylo, yhi = bounds(y1, bin_h, ph, h)  # (R, ph)
        xlo, xhi = bounds(x1, bin_w, pw, w)  # (R, pw)
        ys = jnp.arange(h, dtype=jnp.float32)
        xs = jnp.arange(w, dtype=jnp.float32)
        row_in = (ys[None, None, :] >= ylo[..., None]) & (ys[None, None, :] < yhi[..., None])
        col_in = (xs[None, None, :] >= xlo[..., None]) & (xs[None, None, :] < xhi[..., None])
        roi_feats = feats[batch_idx]  # (R, C, H, W)

        # separable two-stage masked max, one bin index at a time via lax.map:
        # peak memory O(R C H W), never the joint (R, C, ph, pw, H, W) tensor
        # (128 rois x 256ch x 7x7 bins on a 50x50 map would be ~16 GB dense)
        def reduce_rows(i):
            m = jnp.where(
                row_in[:, i, None, :, None], roi_feats, -jnp.inf
            )  # (R, C, H, W)
            return jnp.max(m, axis=2)  # (R, C, W)

        tmp = lax.map(reduce_rows, jnp.arange(ph))  # (ph, R, C, W)

        def reduce_cols(j):
            m = jnp.where(col_in[None, :, j, None, :], tmp, -jnp.inf)
            return jnp.max(m, axis=-1)  # (ph, R, C)

        out = lax.map(reduce_cols, jnp.arange(pw))  # (pw, ph, R, C)
        out = out.transpose(2, 3, 1, 0)  # (R, C, ph, pw)
        # empty bins (degenerate rois) -> 0, matching the reference's memset
        return jnp.where(jnp.isfinite(out), out, 0.0), state


class TemporalAveragePooling(AbstractModule):
    """1-D average pool over (N, T, C) (reference:
    ``$DL/nn/TemporalAveragePooling.scala`` — keras AveragePooling1D)."""

    def __init__(self, k_w: int, d_w: Optional[int] = None):
        super().__init__()
        self.k_w = k_w
        self.d_w = d_w if d_w is not None else k_w

    def infer_shape(self, in_spec):
        shape = tuple(in_spec.shape)
        if len(shape) != 3:
            raise ValueError(f"{self.name()}: expects (N, T, C) input, got shape {shape}")
        _check_window(self, shape, (shape[1],), (self.k_w,))
        return self._infer_shape_via_apply(in_spec)

    def _apply(self, params, state, x, training, rng):
        y = lax.reduce_window(
            x, 0.0, lax.add,
            window_dimensions=(1, self.k_w, 1),
            window_strides=(1, self.d_w, 1),
            padding="VALID",
        )
        return (y / self.k_w).astype(x.dtype), state


class VolumetricAveragePooling(AbstractModule):
    """3-D average pool over (N, C, D, H, W) (reference:
    ``$DL/nn/VolumetricAveragePooling.scala``)."""

    def __init__(self, k_t: int, k_w: int, k_h: int,
                 d_t: Optional[int] = None, d_w: Optional[int] = None,
                 d_h: Optional[int] = None):
        super().__init__()
        self.k = (k_t, k_h, k_w)
        self.d = (d_t or k_t, d_h or k_h, d_w or k_w)

    def infer_shape(self, in_spec):
        shape = tuple(in_spec.shape)
        if len(shape) != 5:
            raise ValueError(f"{self.name()}: expects NCDHW input, got shape {shape}")
        _check_window(self, shape, shape[2:], self.k)
        return self._infer_shape_via_apply(in_spec)

    def _apply(self, params, state, x, training, rng):
        y = lax.reduce_window(
            x, 0.0, lax.add,
            window_dimensions=(1, 1, *self.k),
            window_strides=(1, 1, *self.d),
            padding="VALID",
        )
        return (y / float(self.k[0] * self.k[1] * self.k[2])).astype(x.dtype), state
