"""Detection / MaskRCNN building blocks.

Reference (SURVEY.md §2.2 "attention-era extras"): the MaskRCNN pieces under
``$DL/nn/``: ``Anchor.scala``, ``Nms.scala``, ``BoxUtil``/``BboxUtil``,
``Pooler.scala`` (multi-level RoiAlign), ``FPN.scala``, ``RegionProposal``,
``BoxHead``, ``MaskHead``.

TPU-native design: everything is STATIC-SHAPE jax. The reference's NMS is a
C-style loop over a dynamic candidate list; here it is a fixed-iteration
``lax.fori_loop`` over score-sorted boxes producing exactly ``max_output``
indices (padded with -1) — compilable, differentiable-adjacent, and
batchable with ``vmap``. RoiAlign gathers a fixed sample grid and bilinearly
interpolates — no data-dependent shapes anywhere.

Box convention: (x1, y1, x2, y2) corner boxes, half-open interval semantics
with the +1 Torch legacy OFF (the modern convention the reference's later
maskrcnn code uses).
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .conv import SpatialConvolution
from .linear import Linear
from .module import AbstractModule, Container

# ---------------------------------------------------------------- box utils


def bbox_area(boxes: jax.Array) -> jax.Array:
    """(N, 4) corner boxes -> (N,) areas (clamped at 0)."""
    w = jnp.maximum(boxes[:, 2] - boxes[:, 0], 0.0)
    h = jnp.maximum(boxes[:, 3] - boxes[:, 1], 0.0)
    return w * h


def bbox_iou(a: jax.Array, b: jax.Array) -> jax.Array:
    """(N, 4) x (M, 4) -> (N, M) IoU matrix."""
    lt = jnp.maximum(a[:, None, :2], b[None, :, :2])
    rb = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = jnp.maximum(rb - lt, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    union = bbox_area(a)[:, None] + bbox_area(b)[None, :] - inter
    return inter / jnp.maximum(union, 1e-10)


def bbox_encode(reference: jax.Array, proposals: jax.Array,
                weights: Sequence[float] = (1.0, 1.0, 1.0, 1.0)) -> jax.Array:
    """Boxes -> regression deltas (dx, dy, dw, dh) w.r.t. proposals."""
    wx, wy, ww, wh = weights
    pw = proposals[:, 2] - proposals[:, 0]
    ph = proposals[:, 3] - proposals[:, 1]
    px = proposals[:, 0] + 0.5 * pw
    py = proposals[:, 1] + 0.5 * ph
    gw = reference[:, 2] - reference[:, 0]
    gh = reference[:, 3] - reference[:, 1]
    gx = reference[:, 0] + 0.5 * gw
    gy = reference[:, 1] + 0.5 * gh
    return jnp.stack([
        wx * (gx - px) / jnp.maximum(pw, 1e-6),
        wy * (gy - py) / jnp.maximum(ph, 1e-6),
        ww * jnp.log(jnp.maximum(gw, 1e-6) / jnp.maximum(pw, 1e-6)),
        wh * jnp.log(jnp.maximum(gh, 1e-6) / jnp.maximum(ph, 1e-6)),
    ], axis=1)


def bbox_decode(deltas: jax.Array, boxes: jax.Array,
                weights: Sequence[float] = (1.0, 1.0, 1.0, 1.0),
                clip: float = math.log(1000.0 / 16)) -> jax.Array:
    """Regression deltas + anchor/proposal boxes -> decoded corner boxes."""
    wx, wy, ww, wh = weights
    bw = boxes[:, 2] - boxes[:, 0]
    bh = boxes[:, 3] - boxes[:, 1]
    bx = boxes[:, 0] + 0.5 * bw
    by = boxes[:, 1] + 0.5 * bh
    dx, dy = deltas[:, 0] / wx, deltas[:, 1] / wy
    dw = jnp.clip(deltas[:, 2] / ww, None, clip)
    dh = jnp.clip(deltas[:, 3] / wh, None, clip)
    cx = dx * bw + bx
    cy = dy * bh + by
    w = jnp.exp(dw) * bw
    h = jnp.exp(dh) * bh
    return jnp.stack([cx - 0.5 * w, cy - 0.5 * h, cx + 0.5 * w, cy + 0.5 * h],
                     axis=1)


def bbox_clip(boxes: jax.Array, height: float, width: float) -> jax.Array:
    return jnp.stack([
        jnp.clip(boxes[:, 0], 0.0, width),
        jnp.clip(boxes[:, 1], 0.0, height),
        jnp.clip(boxes[:, 2], 0.0, width),
        jnp.clip(boxes[:, 3], 0.0, height),
    ], axis=1)


# ---------------------------------------------------------------------- nms


def nms(boxes: jax.Array, scores: jax.Array, iou_threshold: float,
        max_output: int) -> jax.Array:
    """Greedy NMS with STATIC shapes (reference: ``Nms.scala``).

    Returns exactly ``max_output`` indices into ``boxes`` (highest-score
    survivors first, -1 padding). The loop runs over the score-sorted
    candidate list with a suppression mask — O(max_output * N) IoU rows,
    each step fully vectorized on the VPU.
    """
    n = boxes.shape[0]
    order = jnp.argsort(-scores)
    sorted_boxes = boxes[order]
    iou = bbox_iou(sorted_boxes, sorted_boxes)  # (N, N), sorted order

    def body(i, carry):
        alive, out = carry
        # first still-alive candidate
        idx = jnp.argmax(alive)
        any_alive = alive[idx]
        out = out.at[i].set(jnp.where(any_alive, idx, -1))
        # suppress everything overlapping it (including itself)
        suppress = iou[idx] > iou_threshold
        suppress = suppress | (jnp.arange(n) == idx)
        alive = alive & jnp.where(any_alive, ~suppress, True)
        return alive, out

    alive0 = jnp.ones((n,), bool)
    out0 = jnp.full((max_output,), -1, jnp.int32)
    _, picked = lax.fori_loop(0, max_output, body, (alive0, out0))
    # map sorted positions back to caller indices, keep -1 padding
    return jnp.where(picked >= 0, order[jnp.clip(picked, 0)], -1)


# ------------------------------------------------------------------ anchors


class Anchor:
    """Anchor-grid generator (reference: ``Anchor.scala``).

    ``sizes`` x ``ratios`` base anchors, tiled over an (Hf, Wf) feature grid
    with the given stride; returns (Hf * Wf * A, 4) corner boxes, row-major
    over (y, x, anchor) like the reference.
    """

    def __init__(self, ratios: Sequence[float], sizes: Sequence[float]):
        self.ratios = list(ratios)
        self.sizes = list(sizes)

    def base_anchors(self) -> np.ndarray:
        out = []
        for size in self.sizes:
            area = float(size) * float(size)
            for ratio in self.ratios:
                w = math.sqrt(area / ratio)
                h = w * ratio
                out.append([-w / 2, -h / 2, w / 2, h / 2])
        return np.asarray(out, np.float32)

    def generate(self, feat_h: int, feat_w: int, stride: float) -> jax.Array:
        base = jnp.asarray(self.base_anchors())  # (A, 4)
        shift_x = (jnp.arange(feat_w) + 0.5) * stride
        shift_y = (jnp.arange(feat_h) + 0.5) * stride
        sx, sy = jnp.meshgrid(shift_x, shift_y)  # (Hf, Wf)
        shifts = jnp.stack([sx, sy, sx, sy], axis=-1).reshape(-1, 1, 4)
        return (shifts + base[None]).reshape(-1, 4)


# ----------------------------------------------------------------- RoiAlign


def roi_align(features: jax.Array, rois: jax.Array, output_size: Tuple[int, int],
              spatial_scale: float, sampling_ratio: int = 2) -> jax.Array:
    """RoiAlign over (C, H, W) features + (R, 4) corner rois -> (R, C, ph, pw).

    Bilinear sampling on a fixed ``sampling_ratio^2`` grid per output bin
    (reference: the Pooler's roialign). Pure gather + lerp, static shapes.
    """
    c, h, w = features.shape
    ph, pw = output_size
    s = sampling_ratio
    boxes = rois * spatial_scale
    x1, y1, x2, y2 = boxes[:, 0], boxes[:, 1], boxes[:, 2], boxes[:, 3]
    roi_w = jnp.maximum(x2 - x1, 1.0)
    roi_h = jnp.maximum(y2 - y1, 1.0)
    bin_w = roi_w / pw
    bin_h = roi_h / ph

    # sample positions: (R, ph*s) ys and (R, pw*s) xs
    iy = (jnp.arange(ph * s) + 0.5) / s  # in bin units
    ix = (jnp.arange(pw * s) + 0.5) / s
    ys = y1[:, None] + iy[None, :] * bin_h[:, None]  # (R, ph*s)
    xs = x1[:, None] + ix[None, :] * bin_w[:, None]  # (R, pw*s)

    def bilinear(img, ys, xs):
        """img (C, H, W), ys (Py,), xs (Px,) -> (C, Py, Px)."""
        y0 = jnp.clip(jnp.floor(ys - 0.5), 0, h - 1)
        x0 = jnp.clip(jnp.floor(xs - 0.5), 0, w - 1)
        y1i = jnp.clip(y0 + 1, 0, h - 1).astype(jnp.int32)
        x1i = jnp.clip(x0 + 1, 0, w - 1).astype(jnp.int32)
        wy = jnp.clip(ys - 0.5 - y0, 0.0, 1.0)
        wx = jnp.clip(xs - 0.5 - x0, 0.0, 1.0)
        y0i, x0i = y0.astype(jnp.int32), x0.astype(jnp.int32)
        g = lambda yy, xx: img[:, yy][:, :, xx]  # (C, Py, Px)
        top = g(y0i, x0i) * (1 - wx)[None, None, :] + g(y0i, x1i) * wx[None, None, :]
        bot = g(y1i, x0i) * (1 - wx)[None, None, :] + g(y1i, x1i) * wx[None, None, :]
        return top * (1 - wy)[None, :, None] + bot * wy[None, :, None]

    sampled = jax.vmap(lambda yy, xx: bilinear(features, yy, xx))(ys, xs)
    # (R, C, ph*s, pw*s) -> average each s x s sample block
    sampled = sampled.reshape(-1, c, ph, s, pw, s)
    return sampled.mean(axis=(3, 5))


def _canonical_level_index(scales: Sequence[float]) -> int:
    """Index of the canonical 1/16-scale (FPN level 4) within ``scales``."""
    for i, s in enumerate(scales):
        if abs(s - 1.0 / 16) < 1e-9:
            return i
    return min(2, len(scales) - 1)


def multilevel_roi_align(feats, rois, scales: Sequence[float],
                         output_size: Tuple[int, int],
                         sampling_ratio: int = 2) -> jax.Array:
    """RoiAlign each roi on its FPN-assigned level (the Pooler core, shared
    with model assemblies).

    Assignment heuristic: canonical level 4 (1/16 scale) gets 224²-area
    rois, ±1 level per octave of sqrt(area); compute-all-select-one is the
    XLA-native (static-shape) form of the reference's per-level
    gather/scatter.
    """
    n_levels = len(scales)
    area = bbox_area(rois)
    target = jnp.floor(4.0 + jnp.log2(jnp.sqrt(jnp.maximum(area, 1e-6))
                                      / 224.0 + 1e-6))
    idx = jnp.clip(target - 4 + _canonical_level_index(scales),
                   0, n_levels - 1).astype(jnp.int32)
    pooled = jnp.stack([
        roi_align(f, rois, output_size, s, sampling_ratio)
        for f, s in zip(feats, scales)
    ])  # (L, R, C, ph, pw)
    return jnp.take_along_axis(
        pooled, idx[None, :, None, None, None], axis=0
    )[0]


class Pooler(AbstractModule):
    """Multi-level RoiAlign pooler (reference: ``Pooler.scala``).

    Input: Table(features: list of (C, Hi, Wi) FPN levels, rois (R, 4)).
    """

    accepts_table_input = True  # consumes a multi-parent Table when graph-wired

    def __init__(self, output_size: Tuple[int, int],
                 scales: Sequence[float], sampling_ratio: int = 2):
        super().__init__()
        self.output_size = tuple(output_size)
        self.scales = list(scales)
        self.sampling_ratio = sampling_ratio

    def _apply(self, params, state, x, training, rng):
        from ..utils.table import Table

        feats, rois = (x.to_list() if isinstance(x, Table) else list(x))[:2]
        out = multilevel_roi_align(feats, rois, self.scales,
                                   self.output_size, self.sampling_ratio)
        return out, state


# ---------------------------------------------------------------------- FPN


class FPN(Container):
    """Feature Pyramid Network neck (reference: ``FPN.scala``).

    Input: list of backbone feature maps (N, Ci, Hi, Wi), coarsest last.
    Output: list of (N, out_channels, Hi, Wi) maps — lateral 1x1 convs plus
    top-down nearest-neighbor upsampling and 3x3 output smoothing.
    """

    accepts_table_input = True  # consumes a multi-parent Table when graph-wired

    def __init__(self, in_channels: Sequence[int], out_channels: int = 256):
        laterals = [SpatialConvolution(c, out_channels, 1, 1)
                    for c in in_channels]
        smooths = [SpatialConvolution(out_channels, out_channels, 3, 3,
                                      pad_w=1, pad_h=1)
                   for _ in in_channels]
        super().__init__(*laterals, *smooths)
        self.n_levels = len(in_channels)
        self.out_channels = out_channels

    def build(self, rng, in_specs):
        for i, (m, spec) in enumerate(zip(self.modules[: self.n_levels],
                                          in_specs)):
            mid = m.build(jax.random.fold_in(rng, i), spec)
            self.modules[self.n_levels + i].build(
                jax.random.fold_in(rng, 1000 + i), mid
            )
        self._built = True
        return [
            jax.ShapeDtypeStruct(
                spec.shape[:1] + (self.out_channels,) + spec.shape[2:],
                spec.dtype,
            )
            for spec in in_specs
        ]

    def _apply(self, params, state, xs, training, rng):
        new_state = dict(state)
        lat = []
        for i, x in enumerate(xs):
            m = self.modules[i]
            y = self._child_apply(m, x, training, rng, params, state, new_state)
            lat.append(y)
        # top-down pathway, coarsest first; ceil-repeat then crop handles
        # odd pyramid sizes (e.g. 25 over 13 from ceil-mode strides)
        merged = [lat[-1]]
        for i in range(len(lat) - 2, -1, -1):
            up = merged[0]
            target = lat[i]
            scale_h = -(-target.shape[2] // up.shape[2])
            scale_w = -(-target.shape[3] // up.shape[3])
            up = jnp.repeat(jnp.repeat(up, scale_h, axis=2), scale_w, axis=3)
            merged.insert(0, target + up[:, :, : target.shape[2],
                                         : target.shape[3]])
        outs = []
        for i, y in enumerate(merged):
            m = self.modules[self.n_levels + i]
            o = self._child_apply(m, y, training, rng, params, state, new_state)
            outs.append(o)
        return outs, new_state


# -------------------------------------------------------------------- heads


class RegionProposal(Container):
    """RPN head + proposal decoding (reference: ``RegionProposal.scala``).

    A conv tower scores A anchors per location and regresses deltas; the
    module decodes, clips, and NMS-selects a fixed ``post_nms_top_n`` set of
    proposal boxes per image — all static shapes.
    """

    accepts_table_input = True  # consumes a multi-parent Table when graph-wired

    def __init__(self, in_channels: int, anchor: Anchor, stride: float = 16.0,
                 pre_nms_top_n: int = 1000, post_nms_top_n: int = 100,
                 nms_threshold: float = 0.7):
        a = len(anchor.ratios) * len(anchor.sizes)
        conv = SpatialConvolution(in_channels, in_channels, 3, 3, pad_w=1, pad_h=1)
        cls_head = SpatialConvolution(in_channels, a, 1, 1)
        box_head = SpatialConvolution(in_channels, a * 4, 1, 1)
        super().__init__(conv, cls_head, box_head)
        self.anchor = anchor
        self.stride = stride
        self.pre_nms_top_n = pre_nms_top_n
        self.post_nms_top_n = post_nms_top_n
        self.nms_threshold = nms_threshold

    def build(self, rng, in_spec):
        mid = self.modules[0].build(jax.random.fold_in(rng, 0), in_spec)
        self.modules[1].build(jax.random.fold_in(rng, 1), mid)
        self.modules[2].build(jax.random.fold_in(rng, 2), mid)
        self._built = True
        n = in_spec.shape[0]
        return jax.ShapeDtypeStruct((n, self.post_nms_top_n, 4),
                                    jnp.float32)

    def _apply(self, params, state, x, training, rng):
        conv, cls_head, box_head = self.modules
        new_state = dict(state)
        t = self._child_apply(conv, x, training, rng, params, state, new_state)
        t = jnp.maximum(t, 0.0)
        logits = self._child_apply(cls_head, t, training, rng, params, state, new_state)
        deltas = self._child_apply(box_head, t, training, rng, params, state, new_state)
        n, a, hf, wf = logits.shape
        anchors = self.anchor.generate(hf, wf, self.stride)  # (H*W*A, 4)
        img_h, img_w = hf * self.stride, wf * self.stride

        def per_image(lg, dl):
            scores = lg.transpose(1, 2, 0).reshape(-1)  # (H*W*A,) row-major
            d = dl.reshape(a, 4, hf, wf).transpose(2, 3, 0, 1).reshape(-1, 4)
            k = min(self.pre_nms_top_n, scores.shape[0])
            top_scores, top_idx = lax.top_k(scores, k)
            boxes = bbox_decode(d[top_idx], anchors[top_idx])
            boxes = bbox_clip(boxes, img_h, img_w)
            keep = nms(boxes, top_scores, self.nms_threshold,
                       self.post_nms_top_n)
            return boxes[jnp.clip(keep, 0)] * (keep >= 0)[:, None]

        return jax.vmap(per_image)(logits, deltas), new_state


class BoxHead(Container):
    """Per-roi classification + box regression head (reference:
    ``BoxHead.scala``): two FC layers then class scores + per-class deltas."""

    accepts_table_input = True  # consumes a multi-parent Table when graph-wired

    def __init__(self, in_features: int, fc_dim: int, n_classes: int):
        super().__init__(
            Linear(in_features, fc_dim),
            Linear(fc_dim, fc_dim),
            Linear(fc_dim, n_classes),
            Linear(fc_dim, n_classes * 4),
        )
        self.n_classes = n_classes

    def build(self, rng, in_spec):
        r = in_spec.shape[0]
        flat = jax.ShapeDtypeStruct(
            (r, int(np.prod(in_spec.shape[1:]))), in_spec.dtype
        )
        s = self.modules[0].build(jax.random.fold_in(rng, 0), flat)
        s = self.modules[1].build(jax.random.fold_in(rng, 1), s)
        self.modules[2].build(jax.random.fold_in(rng, 2), s)
        self.modules[3].build(jax.random.fold_in(rng, 3), s)
        self._built = True
        return (
            jax.ShapeDtypeStruct((r, self.n_classes), jnp.float32),
            jax.ShapeDtypeStruct((r, self.n_classes * 4), jnp.float32),
        )

    def _apply(self, params, state, x, training, rng):
        f1, f2, cls, box = self.modules
        new_state = dict(state)
        y = x.reshape(x.shape[0], -1)
        y = self._child_apply(f1, y, training, rng, params, state, new_state)
        y = jnp.maximum(y, 0.0)
        y = self._child_apply(f2, y, training, rng, params, state, new_state)
        y = jnp.maximum(y, 0.0)
        scores = self._child_apply(cls, y, training, rng, params, state, new_state)
        deltas = self._child_apply(box, y, training, rng, params, state, new_state)
        return (scores, deltas), new_state


class MaskHead(Container):
    """Per-roi mask predictor (reference: ``MaskHead.scala``): conv tower +
    deconv upsample + per-class mask logits."""

    accepts_table_input = True  # consumes a multi-parent Table when graph-wired

    def __init__(self, in_channels: int, dim: int, n_convs: int,
                 n_classes: int):
        from .conv import SpatialFullConvolution

        convs = []
        c = in_channels
        for _ in range(n_convs):
            convs.append(SpatialConvolution(c, dim, 3, 3, pad_w=1, pad_h=1))
            c = dim
        deconv = SpatialFullConvolution(dim, dim, 2, 2, 2, 2)
        predictor = SpatialConvolution(dim, n_classes, 1, 1)
        super().__init__(*convs, deconv, predictor)
        self.n_convs = n_convs

    def build(self, rng, in_spec):
        s = in_spec
        for i, m in enumerate(self.modules):
            s = m.build(jax.random.fold_in(rng, i), s)
        self._built = True
        return s

    def _apply(self, params, state, x, training, rng):
        y = x
        new_state = dict(state)
        for i, m in enumerate(self.modules):
            y = self._child_apply(m, y, training, rng, params, state, new_state)
            if i <= self.n_convs:  # relu after convs + deconv, not the predictor
                y = jnp.maximum(y, 0.0)
        return y, new_state


# ------------------------------------------------------- training machinery


def match_targets(boxes: jax.Array, gt_boxes: jax.Array, gt_valid: jax.Array,
                  high_threshold: float = 0.7,
                  low_threshold: float = 0.3,
                  allow_low_quality: bool = True) -> jax.Array:
    """Assign each anchor/proposal a ground-truth index (reference: the
    Matcher inside ``RegionProposal``/``BoxHead`` training).

    Returns (N,) int32: >=0 = matched gt index, -1 = negative (background),
    -2 = ignore (between thresholds). ``gt_valid`` masks padded gt rows —
    everything static-shape. ``allow_low_quality`` keeps the best anchor per
    gt even below the threshold (the reference's low-quality-match rule).
    """
    iou = bbox_iou(boxes, gt_boxes)  # (N, G)
    iou = jnp.where(gt_valid[None, :].astype(bool), iou, -1.0)
    best_gt = jnp.argmax(iou, axis=1).astype(jnp.int32)  # (N,)
    best_iou = jnp.max(iou, axis=1)
    match = jnp.where(best_iou >= high_threshold, best_gt, -1)
    match = jnp.where(
        (best_iou >= low_threshold) & (best_iou < high_threshold), -2, match
    )
    if allow_low_quality:
        # the argmax anchor of each valid gt is forced positive; .max (not
        # .set) so a padded gt whose argmax collides on the same anchor
        # cannot scatter False over a valid gt's True (duplicate-index
        # scatter order is implementation-defined)
        best_anchor_per_gt = jnp.argmax(iou, axis=0)  # (G,)
        forced = jnp.zeros_like(match, bool)
        forced = forced.at[best_anchor_per_gt].max(gt_valid.astype(bool))
        match = jnp.where(forced, best_gt, match)
    return match


def sample_matches(match: jax.Array, rng: jax.Array, batch_size: int,
                   positive_fraction: float = 0.5):
    """Random positive/negative subsample weights (reference: the
    BalancedPositiveNegativeSampler). Static shapes: returns float (N,)
    weights (1.0 for sampled anchors) for the loss, never index lists.
    """
    n = match.shape[0]
    k_pos = int(round(batch_size * positive_fraction))
    pos = match >= 0
    neg = match == -1
    kp, kn = jax.random.split(rng)
    pos_rank = jnp.argsort(
        jnp.where(pos, jax.random.uniform(kp, (n,)), 2.0)
    )  # random order among positives, padding last
    neg_rank = jnp.argsort(jnp.where(neg, jax.random.uniform(kn, (n,)), 2.0))
    n_pos = jnp.minimum(jnp.sum(pos), k_pos)
    n_neg = jnp.minimum(jnp.sum(neg), batch_size - n_pos)
    pos_w = jnp.zeros((n,)).at[pos_rank].set(
        (jnp.arange(n) < n_pos).astype(jnp.float32)
    )
    neg_w = jnp.zeros((n,)).at[neg_rank].set(
        (jnp.arange(n) < n_neg).astype(jnp.float32)
    )
    return pos_w, neg_w


def smooth_l1(x: jax.Array, beta: float = 1.0 / 9) -> jax.Array:
    ax = jnp.abs(x)
    return jnp.where(ax < beta, 0.5 * ax * ax / beta, ax - 0.5 * beta)


def rpn_loss(objectness: jax.Array, deltas: jax.Array, anchors: jax.Array,
             gt_boxes: jax.Array, gt_valid: jax.Array, rng: jax.Array,
             batch_size: int = 256, positive_fraction: float = 0.5):
    """RPN objectness BCE + box smooth-L1 on sampled anchors (reference:
    RegionProposal's training loss). All inputs per-image, static shapes:
    objectness (N,), deltas (N, 4), anchors (N, 4), gt (G, 4) + valid (G,).
    Returns (cls_loss, box_loss) scalars.
    """
    match = match_targets(anchors, gt_boxes, gt_valid)
    pos_w, neg_w = sample_matches(match, rng, batch_size, positive_fraction)
    labels = (match >= 0).astype(jnp.float32)
    w = pos_w + neg_w
    cls = jnp.sum(
        w * (jnp.logaddexp(0.0, objectness) - labels * objectness)
    ) / jnp.maximum(jnp.sum(w), 1.0)
    matched_gt = gt_boxes[jnp.clip(match, 0)]
    targets = bbox_encode(matched_gt, anchors)
    # box term normalized by the TOTAL sampled count (pos+neg), matching the
    # reference loss balance — not by the positive count alone
    box = jnp.sum(
        pos_w[:, None] * smooth_l1(deltas - targets)
    ) / jnp.maximum(jnp.sum(w), 1.0)
    return cls, box


def fast_rcnn_loss(class_logits: jax.Array, box_deltas: jax.Array,
                   proposals: jax.Array, gt_boxes: jax.Array,
                   gt_labels: jax.Array, gt_valid: jax.Array,
                   rng: jax.Array, batch_size: int = 128,
                   positive_fraction: float = 0.25):
    """Box-head loss (reference: BoxHead training): softmax CE over sampled
    proposals (label 0 = background) + per-class box smooth-L1 on positives.

    class_logits (N, C), box_deltas (N, C*4), proposals (N, 4),
    gt_boxes (G, 4), gt_labels (G,) 1-based class ids, gt_valid (G,).
    """
    n, c = class_logits.shape
    match = match_targets(proposals, gt_boxes, gt_valid,
                          high_threshold=0.5, low_threshold=0.5,
                          allow_low_quality=False)
    pos_w, neg_w = sample_matches(match, rng, batch_size, positive_fraction)
    w = pos_w + neg_w
    labels = jnp.where(match >= 0, gt_labels[jnp.clip(match, 0)], 0)
    logp = jax.nn.log_softmax(class_logits, axis=-1)
    cls = -jnp.sum(w * logp[jnp.arange(n), labels]) / jnp.maximum(
        jnp.sum(w), 1.0
    )
    matched_gt = gt_boxes[jnp.clip(match, 0)]
    targets = bbox_encode(matched_gt, proposals)
    per_class = box_deltas.reshape(n, c, 4)
    picked = jnp.take_along_axis(
        per_class, labels[:, None, None].repeat(4, 2), axis=1
    )[:, 0]
    # normalized by total sampled count, same balance as the reference
    box = jnp.sum(
        pos_w[:, None] * smooth_l1(picked - targets)
    ) / jnp.maximum(jnp.sum(w), 1.0)
    return cls, box
