"""MaskRCNN — two-stage detector with mask branch.

Reference (SURVEY.md §2.2 "attention-era extras" / §2.9 "maskrcnn (0.10+)"):
the reference assembles its ``MaskRCNN`` from the pieces under ``$DL/nn/``
(``FPN``, ``RegionProposal``, ``Pooler``, ``BoxHead``, ``MaskHead``,
``Anchor``, ``Nms``). This module does the same assembly over the TPU-native
pieces in ``bigdl_tpu.nn.detection`` — every stage is static-shape jax, so
the whole inference path jit-compiles: a fixed ``post_nms_top_n`` proposal
budget flows through RoiAlign/heads, and final detections are a fixed-size
(boxes, scores, labels, masks) set with score 0 padding.

This is the INFERENCE assembly (detector training needs target-matching
machinery the reference also keeps outside these modules).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.detection import (
    Anchor,
    bbox_clip,
    bbox_decode,
    multilevel_roi_align,
    nms,
)
from ..nn.module import Container, run_child


def _conv_backbone(channels: Sequence[int]):
    """Small strided-conv backbone emitting one feature map per level
    (stand-in for the reference's ResNet-C4/FPN backbones; any module list
    with matching channels can replace it)."""
    levels = []
    c_in = 3
    for i, c in enumerate(channels):
        levels.append(
            nn.Sequential(
                nn.SpatialConvolution(c_in, c, 3, 3, 2, 2, 1, 1),
                nn.ReLU(),
                nn.SpatialConvolution(c, c, 3, 3, 1, 1, 1, 1),
                nn.ReLU(),
            ).set_name(f"backbone_level{i}")
        )
        c_in = c
    return levels


class MaskRCNN(Container):
    """Backbone → FPN → RPN → RoiAlign → Box/Mask heads (reference:
    the MaskRCNN assembly of ``$DL/nn`` detection pieces).

    ``forward(images)`` with images (N, 3, H, W) returns a Table of
    (boxes (N, D, 4), scores (N, D), labels (N, D), masks (N, D, C, 2m, 2m))
    where D = ``detections_per_image`` — fixed shapes, zero-score padding.
    """

    def __init__(
        self,
        n_classes: int,
        backbone_channels: Sequence[int] = (32, 64, 128, 256),
        fpn_channels: int = 128,
        anchor_ratios: Sequence[float] = (0.5, 1.0, 2.0),
        anchor_size: float = 32.0,
        pre_nms_top_n: int = 256,
        post_nms_top_n: int = 64,
        detections_per_image: int = 16,
        box_pool: int = 7,
        mask_pool: int = 14,
        score_threshold: float = 0.05,
        nms_threshold: float = 0.5,
    ):
        backbone = _conv_backbone(backbone_channels)
        fpn = nn.FPN(list(backbone_channels), fpn_channels).set_name("fpn")
        # one RPN over the finest FPN level (the reference runs one head
        # shared across levels; single-level keeps the assembly compact
        # while the per-level machinery stays available in nn.detection)
        finest_stride = 2.0  # backbone level 0 downsamples once (1/2 scale)
        rpn = nn.RegionProposal(
            fpn_channels,
            Anchor(list(anchor_ratios), [anchor_size]),
            stride=finest_stride,
            pre_nms_top_n=pre_nms_top_n,
            post_nms_top_n=post_nms_top_n,
        ).set_name("rpn")
        box_head = nn.BoxHead(
            fpn_channels * box_pool * box_pool, 256, n_classes
        ).set_name("box_head")
        mask_head = nn.MaskHead(
            fpn_channels, 128, 2, n_classes
        ).set_name("mask_head")
        super().__init__(*backbone, fpn, rpn, box_head, mask_head)
        self.n_backbone = len(backbone)
        self.n_classes = n_classes
        self.detections_per_image = detections_per_image
        self.box_pool = box_pool
        self.mask_pool = mask_pool
        self.score_threshold = score_threshold
        self.nms_threshold = nms_threshold
        self.fpn_scales = [1.0 / (2 ** (i + 1))
                           for i in range(len(backbone_channels))]

    # ------------------------------------------------------------------ build
    def build(self, rng, in_spec):
        spec = in_spec
        specs = []
        for i in range(self.n_backbone):
            spec = self.modules[i].build(jax.random.fold_in(rng, i), spec)
            specs.append(spec)
        fpn = self.modules[self.n_backbone]
        fpn_specs = fpn.build(jax.random.fold_in(rng, 100), specs)
        rpn = self.modules[self.n_backbone + 1]
        rpn.build(jax.random.fold_in(rng, 101), fpn_specs[0])
        c = fpn_specs[0].shape[1]
        box_head = self.modules[self.n_backbone + 2]
        box_head.build(
            jax.random.fold_in(rng, 102),
            jax.ShapeDtypeStruct(
                (self.detections_per_image, c, self.box_pool, self.box_pool),
                jnp.float32,
            ),
        )
        mask_head = self.modules[self.n_backbone + 3]
        mask_head.build(
            jax.random.fold_in(rng, 103),
            jax.ShapeDtypeStruct(
                (self.detections_per_image, c, self.mask_pool, self.mask_pool),
                jnp.float32,
            ),
        )
        self._built = True
        n, d = in_spec.shape[0], self.detections_per_image
        from ..utils.table import T

        return T(
            jax.ShapeDtypeStruct((n, d, 4), jnp.float32),
            jax.ShapeDtypeStruct((n, d), jnp.float32),
            jax.ShapeDtypeStruct((n, d), jnp.int32),
            jax.ShapeDtypeStruct(
                (n, d, self.n_classes, 2 * self.mask_pool, 2 * self.mask_pool),
                jnp.float32,
            ),
        )

    # ------------------------------------------------------------------ apply
    def _apply(self, params, state, x, training, rng):
        from ..utils.table import T

        new_state = dict(state)
        feats = []
        y = x
        for i in range(self.n_backbone):
            m = self.modules[i]
            y = self._child_apply(m, y, training, rng, params, state, new_state)
            feats.append(y)
        fpn = self.modules[self.n_backbone]
        fpn_feats = self._child_apply(
            fpn, feats, training, rng, params, state, new_state)
        rpn = self.modules[self.n_backbone + 1]
        proposals = self._child_apply(
            rpn, fpn_feats[0], training, rng, params, state, new_state)  # (N, P, 4)
        box_head = self.modules[self.n_backbone + 2]
        mask_head = self.modules[self.n_backbone + 3]
        img_h = x.shape[2]
        img_w = x.shape[3]
        d = self.detections_per_image

        def per_image(levels, props):
            # multi-level RoiAlign for the box head (compute-all-select-one
            # as in nn.detection.Pooler, inlined to reuse `levels`)
            pooled = self._pool(levels, props, self.box_pool)
            (scores, deltas), _ = run_child(
                box_head, params[box_head.name()], state[box_head.name()],
                pooled, training, rng,
            )
            probs = jax.nn.softmax(scores, axis=-1)  # (P, C); class 0 = bg
            best_cls = jnp.argmax(probs[:, 1:], axis=1) + 1  # (P,)
            best_score = jnp.take_along_axis(
                probs, best_cls[:, None], axis=1
            )[:, 0]
            best_deltas = jax.vmap(
                lambda dl, c: jax.lax.dynamic_slice(dl, (c * 4,), (4,))
            )(deltas, best_cls)
            boxes = bbox_clip(
                bbox_decode(best_deltas, props), img_h, img_w
            )
            best_score = jnp.where(best_score >= self.score_threshold,
                                   best_score, 0.0)
            keep = nms(boxes, best_score, self.nms_threshold, d)
            valid = keep >= 0
            sel = jnp.clip(keep, 0)
            det_boxes = boxes[sel] * valid[:, None]
            det_scores = best_score[sel] * valid
            det_labels = (best_cls[sel] * valid).astype(jnp.int32)
            mask_in = self._pool(levels, det_boxes, self.mask_pool)
            masks, _ = run_child(
                mask_head, params[mask_head.name()], state[mask_head.name()],
                mask_in, training, rng,
            )
            return det_boxes, det_scores, det_labels, masks

        boxes, scores, labels, masks = jax.vmap(per_image)(
            [f for f in fpn_feats], proposals
        )
        return T(boxes, scores, labels, masks), new_state

    def _pool(self, levels, rois, size):
        return multilevel_roi_align(levels, rois, self.fpn_scales,
                                    (size, size))
