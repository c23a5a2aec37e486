"""Criterion (loss) zoo — reference: ``$DL/nn/abstractnn/AbstractCriterion.scala`` and
one file per criterion under ``$DL/nn/`` (ClassNLLCriterion.scala, MSECriterion.scala...).

The reference hand-writes ``updateGradInput`` per criterion; here ``backward`` is
``jax.grad`` of the pure loss. ``size_average`` semantics follow the reference
(mean over batch by default; sum when False).

Label convention: the reference is Torch-1-based (targets in 1..C). This framework
defaults to 0-based labels (idiomatic numpy/jax); pass ``one_based_label=True`` for
strict reference parity (the model-zoo examples use 0-based throughout).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import precision
from ..utils.table import Table


class AbstractCriterion:
    """Loss base: ``forward(input,target)->loss``, ``backward->gradInput``."""

    def __init__(self):
        self.output = None
        self.grad_input = None

    def _apply(self, input, target):  # pure scalar loss
        raise NotImplementedError

    def counted(self, input, target):
        """``(loss, {name: scalar})``: the loss and what of its parts the
        step record should carry. ``LocalOptimizer``'s loss function writes
        each part into the model state's ``_counters`` slot of that name
        (``AbstractModule.counters_tree``); a criterion with no parts is
        ``_apply`` and nothing else."""
        return self._apply(input, target), {}

    def unreduced(self, input, target):
        """Per-sample loss decomposition, or ``None`` when the criterion has
        no row-wise form.

        Returns ``(per, denom)`` arrays whose leading axis is the batch axis
        (a flattened ``batch*positions`` leading axis is also allowed), such
        that the scalar loss equals ``sum(per) / max(sum(denom), eps)`` when
        ``size_average`` else ``sum(per)``. The optimizer's ragged-batch seam
        uses this to pad the final short batch of an epoch to the step's
        static shape and mask the pad rows out of the loss EXACTLY — one XLA
        compilation serves every batch (docs/performance.md). Criterions that
        return ``None`` fall back to the reference semantics: ragged train
        batches are dropped.
        """
        return None

    def supports_unreduced(self) -> bool:
        """Static capability probe for the ragged-batch seam: True when
        ``unreduced`` will return a decomposition for this INSTANCE (checked
        before any tracing, so the pad-vs-drop policy is fixed up front)."""
        return type(self).unreduced is not AbstractCriterion.unreduced

    def forward(self, input, target):
        input = jax.tree_util.tree_map(jnp.asarray, input)
        self.output = self._apply(input, target)
        return self.output

    def __call__(self, input, target):
        return self.forward(input, target)

    def backward(self, input, target):
        input = jax.tree_util.tree_map(jnp.asarray, input)
        self.grad_input = jax.grad(lambda i: self._apply(i, target))(input)
        return self.grad_input


def _reduce(x, size_average: bool):
    return jnp.mean(x) if size_average else jnp.sum(x)


class ClassNLLCriterion(AbstractCriterion):
    """NLL over log-probabilities (reference: $DL/nn/ClassNLLCriterion.scala).

    ``logProbAsInput=True`` expects log-softmax outputs (the LeNet/ResNet recipes pair
    it with LogSoftMax). ``weights`` is per-class. ``padding_value`` marks ignored
    targets (contributes 0 loss, reference semantics for padded sequence batches).
    """

    def __init__(
        self,
        weights: Optional[jnp.ndarray] = None,
        size_average: bool = True,
        log_prob_as_input: bool = True,
        one_based_label: bool = False,
        padding_value: Optional[int] = None,
    ):
        super().__init__()
        self.weights = None if weights is None else jnp.asarray(weights)
        self.size_average = size_average
        self.log_prob_as_input = log_prob_as_input
        self.one_based_label = one_based_label
        self.padding_value = padding_value

    def unreduced(self, input, target):
        input = precision.to_float(input)  # loss head is always fp32
        logp = input if self.log_prob_as_input else jnp.log(jnp.clip(input, 1e-8))
        target = jnp.asarray(target).astype(jnp.int32).reshape(-1)
        idx = target - 1 if self.one_based_label else target
        logp = logp.reshape(-1, logp.shape[-1])
        n_classes = logp.shape[-1]
        safe_idx = jnp.clip(idx, 0, n_classes - 1)
        per = -jnp.take_along_axis(logp, safe_idx[:, None], axis=-1)[:, 0]
        w = jnp.ones_like(per) if self.weights is None else self.weights[safe_idx]
        padded = (
            jnp.zeros_like(target, bool)
            if self.padding_value is None
            else target == self.padding_value
        )
        w = jnp.where(padded, 0.0, w)
        # out-of-range labels can't raise under jit (reference errors eagerly);
        # poison the loss with NaN instead of silently training on a clipped label
        invalid = (~padded) & ((idx < 0) | (idx >= n_classes))
        per = jnp.where(invalid, jnp.nan, per * w)
        return per, w

    def _apply(self, input, target):
        per, w = self.unreduced(input, target)
        if self.size_average:
            denom = jnp.maximum(jnp.sum(w), 1e-8)
            return jnp.sum(per) / denom
        return jnp.sum(per)


class CrossEntropyCriterion(AbstractCriterion):
    """LogSoftMax + NLL fused (reference: $DL/nn/CrossEntropyCriterion.scala).

    ``label_smoothing`` mixes the one-hot target with the uniform distribution
    (the ImageNet ResNet recipe's smoothing; the reference expresses it via its
    training scripts): loss = (1-ε)·NLL + ε·mean_c(-log p_c).
    """

    def __init__(
        self,
        weights: Optional[jnp.ndarray] = None,
        size_average: bool = True,
        one_based_label: bool = False,
        label_smoothing: float = 0.0,
    ):
        super().__init__()
        self.label_smoothing = float(label_smoothing)
        self._nll = ClassNLLCriterion(
            weights=weights, size_average=size_average, one_based_label=one_based_label
        )

    @property
    def size_average(self) -> bool:
        return self._nll.size_average

    def supports_unreduced(self) -> bool:
        return not (self.label_smoothing != 0.0 and self._nll.weights is not None)

    def unreduced(self, input, target):
        eps = self.label_smoothing
        if eps != 0.0 and self._nll.weights is not None:
            # smoothing's uniform term is an UNWEIGHTED row mean while the NLL
            # term divides by sum(class weights) — no single (per, denom) pair
            # reproduces that mix, so the ragged seam falls back to dropping
            return None
        logp = jax.nn.log_softmax(precision.to_float(input), axis=-1)
        per, w = self._nll.unreduced(logp, target)
        if eps == 0.0:
            return per, w
        uniform = -jnp.mean(logp.reshape(-1, logp.shape[-1]), axis=-1)
        return (1.0 - eps) * per + eps * uniform, w

    def _apply(self, input, target):
        logp = jax.nn.log_softmax(precision.to_float(input), axis=-1)
        nll = self._nll._apply(logp, target)
        eps = self.label_smoothing
        if eps == 0.0:
            return nll
        uniform = -jnp.mean(logp, axis=-1)  # per-sample CE against uniform
        uniform = (
            jnp.mean(uniform) if self._nll.size_average else jnp.sum(uniform)
        )
        return (1.0 - eps) * nll + eps * uniform


@jax.custom_vjp
def _token_cross_entropy(logits, target):
    """Mean over all positions of ``logsumexp(logits) - logits[target]``,
    float32. The gradient is rebuilt from the logits and their logsumexp in
    one elementwise pass (``(softmax - onehot) / positions``), so that no
    second full-size float32 tensor (the log-probabilities, or their
    cotangent) is kept between the two passes."""
    return _token_ce_fwd(logits, target)[0]


def _token_ce_fwd(logits, target):
    logits = precision.to_float(logits)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, target[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked), (logits, lse, target)


def _token_ce_bwd(res, g):
    logits, lse, target = res
    classes = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    d = jnp.exp(logits - lse[..., None]) - (classes == target[..., None])
    return d * (g / lse.size), None


_token_cross_entropy.defvjp(_token_ce_fwd, _token_ce_bwd)


class TokenCrossEntropyCriterion(AbstractCriterion):
    """Token-level cross-entropy of a language model: logits ``(N, T, V)``
    against zero-based int targets ``(N, T)``, mean over all ``N * T``
    positions, softmax statistics in float32 (see ``_token_cross_entropy``).
    Beyond reference: BigDL spells this ``TimeDistributedCriterion(
    CrossEntropyCriterion)``, which holds the log-probabilities as well."""

    def _apply(self, input, target):
        with jax.named_scope("lm_head"):
            return _token_cross_entropy(
                input, jnp.asarray(target).astype(jnp.int32))


@jax.custom_vjp
def _masked_token_cross_entropy(logits, target, valid):
    """``_token_cross_entropy`` over the positions where ``valid`` (bool, the
    targets' shape) holds: their mean, and no gradient elsewhere. The logits
    keep their shape, so that leaving positions out costs no copy of them."""
    return _masked_token_ce_fwd(logits, target, valid)[0]


def _masked_token_ce_fwd(logits, target, valid):
    logits = precision.to_float(logits)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, target[..., None], axis=-1)[..., 0]
    count = jnp.sum(valid.astype(jnp.float32))
    loss = jnp.sum(jnp.where(valid, lse - picked, 0.0)) / count
    return loss, (logits, lse, target, valid, count)


def _masked_token_ce_bwd(res, g):
    logits, lse, target, valid, count = res
    classes = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    d = jnp.exp(logits - lse[..., None]) - (classes == target[..., None])
    return jnp.where(valid[..., None], d * (g / count), 0.0), None, None


_masked_token_cross_entropy.defvjp(_masked_token_ce_fwd, _masked_token_ce_bwd)


class MultiTokenCrossEntropyCriterion(AbstractCriterion):
    """The loss of a language model with one multi-token-prediction module
    (DeepSeek-V3, arXiv:2412.19437 section 2.2): input ``Table(logits,
    logits_1)``, both ``(N, T, V)``, zero-based int targets ``(N, T)`` with
    ``target[t]`` the token after position ``t``;
    ``CE(logits, target) + weight * CE(logits_1[:, :-1], target[:, 1:])``:
    ``logits_1[t]`` predicts the token after next, and the last position, which
    has neither a next input token nor a label, is left out of the second
    mean. ``counted`` reports the second cross-entropy, before ``weight``, as
    ``mtp_loss``."""

    def __init__(self, weight: float = 0.1):
        super().__init__()
        self.weight = float(weight)

    def counted(self, input, target):
        logits, logits_1 = input.to_list() if isinstance(input, Table) \
            else list(input)
        target = jnp.asarray(target).astype(jnp.int32)
        with jax.named_scope("lm_head"):
            main = _token_cross_entropy(logits, target)
        with jax.named_scope("mtp"), jax.named_scope("lm_head"):
            after_next = jnp.concatenate(
                [target[:, 1:], jnp.zeros_like(target[:, :1])], axis=1)
            valid = jnp.broadcast_to(
                jnp.arange(target.shape[1]) < target.shape[1] - 1, target.shape)
            second = _masked_token_cross_entropy(logits_1, after_next, valid)
        return main + self.weight * second, {"mtp_loss": second}

    def _apply(self, input, target):
        return self.counted(input, target)[0]


class MSECriterion(AbstractCriterion):
    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def unreduced(self, input, target):
        per = (input - jnp.asarray(target)) ** 2
        return per, jnp.ones_like(per)

    def _apply(self, input, target):
        return _reduce((input - jnp.asarray(target)) ** 2, self.size_average)


class AbsCriterion(AbstractCriterion):
    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def unreduced(self, input, target):
        per = jnp.abs(input - jnp.asarray(target))
        return per, jnp.ones_like(per)

    def _apply(self, input, target):
        return _reduce(jnp.abs(input - jnp.asarray(target)), self.size_average)


class SmoothL1Criterion(AbstractCriterion):
    """Huber with delta=1 (reference: $DL/nn/SmoothL1Criterion.scala)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def unreduced(self, input, target):
        d = input - jnp.asarray(target)
        a = jnp.abs(d)
        per = jnp.where(a < 1.0, 0.5 * d * d, a - 0.5)
        return per, jnp.ones_like(per)

    def _apply(self, input, target):
        d = input - jnp.asarray(target)
        a = jnp.abs(d)
        per = jnp.where(a < 1.0, 0.5 * d * d, a - 0.5)
        return _reduce(per, self.size_average)


class BCECriterion(AbstractCriterion):
    """Binary cross-entropy on probabilities (reference: $DL/nn/BCECriterion.scala)."""

    def __init__(self, weights=None, size_average: bool = True):
        super().__init__()
        self.weights = None if weights is None else jnp.asarray(weights)
        self.size_average = size_average

    def _apply(self, input, target):
        t = jnp.asarray(target)
        eps = 1e-12
        per = -(t * jnp.log(input + eps) + (1 - t) * jnp.log(1 - input + eps))
        if self.weights is not None:
            per = per * self.weights
        return _reduce(per, self.size_average)


class BCECriterionWithLogits(AbstractCriterion):
    """Numerically-stable sigmoid+BCE (reference era: SigmoidBCECriterion)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def _apply(self, input, target):
        t = jnp.asarray(target)
        per = jnp.maximum(input, 0) - input * t + jnp.log1p(jnp.exp(-jnp.abs(input)))
        return _reduce(per, self.size_average)


class DistKLDivCriterion(AbstractCriterion):
    """KL(target || exp(input)) with log-prob inputs (reference: $DL/nn/DistKLDivCriterion.scala)."""

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def _apply(self, input, target):
        t = jnp.asarray(target)
        per = jnp.where(t > 0, t * (jnp.log(jnp.clip(t, 1e-12)) - input), 0.0)
        n = input.shape[0] if input.ndim > 1 else 1
        return jnp.sum(per) / n if self.size_average else jnp.sum(per)


class MarginRankingCriterion(AbstractCriterion):
    """max(0, -y(x1-x2)+margin); input is a Table(x1, x2) (reference file of same name)."""

    def __init__(self, margin: float = 1.0, size_average: bool = True):
        super().__init__()
        self.margin = margin
        self.size_average = size_average

    def _apply(self, input, target):
        x1, x2 = (input[1], input[2]) if isinstance(input, Table) else (input[0], input[1])
        y = jnp.asarray(target)
        return _reduce(jnp.maximum(0.0, -y * (x1 - x2) + self.margin), self.size_average)


class HingeEmbeddingCriterion(AbstractCriterion):
    def __init__(self, margin: float = 1.0, size_average: bool = True):
        super().__init__()
        self.margin = margin
        self.size_average = size_average

    def _apply(self, input, target):
        y = jnp.asarray(target)
        per = jnp.where(y == 1, input, jnp.maximum(0.0, self.margin - input))
        return _reduce(per, self.size_average)


class CosineEmbeddingCriterion(AbstractCriterion):
    def __init__(self, margin: float = 0.0, size_average: bool = True):
        super().__init__()
        self.margin = margin
        self.size_average = size_average

    def _apply(self, input, target):
        x1, x2 = (input[1], input[2]) if isinstance(input, Table) else (input[0], input[1])
        y = jnp.asarray(target).reshape(-1)
        cos = jnp.sum(x1 * x2, -1) / jnp.clip(
            jnp.linalg.norm(x1, axis=-1) * jnp.linalg.norm(x2, axis=-1), 1e-12
        )
        per = jnp.where(y == 1, 1 - cos, jnp.maximum(0.0, cos - self.margin))
        return _reduce(per, self.size_average)


class MultiLabelSoftMarginCriterion(AbstractCriterion):
    def __init__(self, weights=None, size_average: bool = True):
        super().__init__()
        self.weights = None if weights is None else jnp.asarray(weights)
        self.size_average = size_average

    def _apply(self, input, target):
        t = jnp.asarray(target)
        per = jnp.maximum(input, 0) - input * t + jnp.log1p(jnp.exp(-jnp.abs(input)))
        if self.weights is not None:
            per = per * self.weights
        per = jnp.mean(per, axis=-1)
        return _reduce(per, self.size_average)


class L1Cost(AbstractCriterion):
    """sum |x| ignoring target (reference: $DL/nn/L1Cost.scala)."""

    def _apply(self, input, target):
        return jnp.sum(jnp.abs(input))


class ParallelCriterion(AbstractCriterion):
    """Weighted multi-loss over Tables (reference: $DL/nn/ParallelCriterion.scala)."""

    def __init__(self, repeat_target: bool = False):
        super().__init__()
        self.criterions: List[AbstractCriterion] = []
        self.crit_weights: List[float] = []
        self.repeat_target = repeat_target

    def add(self, criterion: AbstractCriterion, weight: float = 1.0) -> "ParallelCriterion":
        self.criterions.append(criterion)
        self.crit_weights.append(weight)
        return self

    def _apply(self, input, target):
        inputs = input.to_list() if isinstance(input, Table) else list(input)
        if self.repeat_target:
            targets = [target] * len(inputs)
        else:
            targets = target.to_list() if isinstance(target, Table) else list(target)
        total = 0.0
        for c, w, i, t in zip(self.criterions, self.crit_weights, inputs, targets):
            total = total + w * c._apply(i, t)
        return total


class MultiCriterion(AbstractCriterion):
    """Sum of several criterions over the same (input, target) (reference file same name)."""

    def __init__(self):
        super().__init__()
        self.criterions: List[AbstractCriterion] = []
        self.crit_weights: List[float] = []

    def add(self, criterion: AbstractCriterion, weight: float = 1.0) -> "MultiCriterion":
        self.criterions.append(criterion)
        self.crit_weights.append(weight)
        return self

    def _apply(self, input, target):
        total = 0.0
        for c, w in zip(self.criterions, self.crit_weights):
            total = total + w * c._apply(input, target)
        return total


class TimeDistributedCriterion(AbstractCriterion):
    """Apply a criterion per time step over (N, T, ...) (reference file same name)."""

    def __init__(self, criterion: AbstractCriterion, size_average: bool = False, dimension: int = 2):
        super().__init__()
        self.criterion = criterion
        self.size_average = size_average
        self.dimension = dimension

    def _apply(self, input, target):
        # ONE vectorized trace of the inner criterion over the time axis, not
        # T unrolled copies: the unrolled program of an LM step at T=2048
        # took XLA:TPU ~12 minutes to compile (chip run, PR 21)
        per_step = jax.vmap(self.criterion._apply, in_axes=1)(
            input, jnp.asarray(target))
        total = jnp.sum(per_step)
        return total / input.shape[1] if self.size_average else total


class MarginCriterion(AbstractCriterion):
    """Hinge loss for two-class classification: mean/sum of
    ``max(0, margin - x*y)`` with targets in {1, -1}
    (reference: ``$DL/nn/MarginCriterion.scala``; squared=True gives L2-SVM)."""

    def __init__(self, margin: float = 1.0, size_average: bool = True,
                 squared: bool = False):
        super().__init__()
        self.margin = margin
        self.size_average = size_average
        self.squared = squared

    def _apply(self, input, target):
        t = jnp.asarray(target, input.dtype).reshape(input.shape)
        per = jnp.maximum(0.0, self.margin - input * t)
        if self.squared:
            per = per**2
        return _reduce(per, self.size_average)


class MultiLabelMarginCriterion(AbstractCriterion):
    """Multi-class multi-label hinge (reference:
    ``$DL/nn/MultiLabelMarginCriterion.scala``; Torch semantics).

    ``target`` rows list 1-based class indices, zero-padded at the end (only
    indices before the first 0 count). Per sample:
    ``sum_{j in targets} sum_{i not in targets} max(0, 1 - (x[y_j] - x[i])) / dim``.
    """

    def __init__(self, size_average: bool = True):
        super().__init__()
        self.size_average = size_average

    def _apply(self, input, target):
        t = jnp.asarray(target, jnp.int32)
        n, d = input.shape
        # valid = before the first zero in each row
        first_zero = jnp.argmax(jnp.concatenate(
            [t == 0, jnp.ones((n, 1), bool)], axis=1), axis=1)
        valid = jnp.arange(t.shape[1])[None, :] < first_zero[:, None]  # (N, K)
        idx0 = jnp.clip(t - 1, 0, d - 1)  # 0-based target indices
        # is_target[n, i] = class i appears among sample n's valid targets
        onehot = jax.nn.one_hot(idx0, d, dtype=bool) & valid[..., None]
        is_target = jnp.any(onehot, axis=1)  # (N, D)
        x_tgt = jnp.take_along_axis(input, idx0, axis=1)  # (N, K)
        # margins over NON-target classes only
        diff = 1.0 - (x_tgt[:, :, None] - input[:, None, :])  # (N, K, D)
        hinge = jnp.maximum(0.0, diff)
        mask = valid[:, :, None] & ~is_target[:, None, :]
        per = jnp.sum(jnp.where(mask, hinge, 0.0), axis=(1, 2)) / d
        return _reduce(per, self.size_average)


class DiceCoefficientCriterion(AbstractCriterion):
    """1 - Dice overlap, for segmentation
    (reference: ``$DL/nn/DiceCoefficientCriterion.scala``):
    ``1 - (2*sum(x*y) + eps) / (sum(x) + sum(y) + eps)`` per sample."""

    def __init__(self, size_average: bool = True, epsilon: float = 1.0):
        super().__init__()
        self.size_average = size_average
        self.epsilon = epsilon

    def _apply(self, input, target):
        t = jnp.asarray(target, input.dtype).reshape(input.shape)
        axes = tuple(range(1, input.ndim))
        inter = jnp.sum(input * t, axis=axes)
        denom = jnp.sum(input, axis=axes) + jnp.sum(t, axis=axes)
        per = 1.0 - (2.0 * inter + self.epsilon) / (denom + self.epsilon)
        return _reduce(per, self.size_average)


def simplex_coordinates(n: int) -> jnp.ndarray:
    """Vertices of a regular (n-1)-simplex embedded in R^n, one row per class
    (the reference's ClassSimplexCriterion target embedding)."""
    # one-hot vertices centered on their mean, rows normalized: n unit
    # vectors in R^n, pairwise equidistant
    eye = np.eye(n, dtype=np.float32)
    verts = eye - np.mean(eye, axis=0, keepdims=True)
    norms = np.linalg.norm(verts, axis=1, keepdims=True)
    return jnp.asarray(verts / norms)


class ClassSimplexCriterion(AbstractCriterion):
    """MSE against regular-simplex class embeddings (reference:
    ``$DL/nn/ClassSimplexCriterion.scala``): targets are 1-based class ids
    mapped to the vertices of a regular simplex in R^nClasses."""

    def __init__(self, n_classes: int, size_average: bool = True):
        super().__init__()
        if n_classes < 2:
            raise ValueError("ClassSimplexCriterion needs n_classes >= 2")
        self.n_classes = n_classes
        self.size_average = size_average
        self._simplex = simplex_coordinates(n_classes)

    def _apply(self, input, target):
        t = jnp.asarray(target, jnp.int32).reshape(input.shape[0])
        goal = self._simplex[jnp.clip(t - 1, 0, self.n_classes - 1)]
        return _reduce((input - goal) ** 2, self.size_average)
