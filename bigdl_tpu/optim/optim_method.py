"""Optimization methods (reference: one file each under ``$DL/optim``: SGD.scala,
Adam.scala, Adagrad.scala, Adadelta.scala, Adamax.scala, RMSprop.scala, Ftrl.scala...).

TPU-native design: each method is a PURE update — ``init_state(params)`` builds a
slot pytree and ``update(grads, params, slots, lr, step)`` returns new
(params, slots). Both are jit-traceable and shard_map-friendly, so the same method
object drives the single-chip LocalOptimizer, the ZeRO-1-sharded DistriOptimizer
update (each device updates only its parameter shard, mirroring AllReduceParameter's
placement), and eager oracle tests. Hyperparameters live on the object (static under
jit); learning rate arrives as a traced scalar so schedules never retrace.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from .schedules import Default, LearningRateSchedule

_tm = jax.tree_util.tree_map


class OptimMethod:
    """Base optimizer. ``state`` here is the host-side state table (epoch/neval/...)
    — the reference keeps the same table inside each OptimMethod instance."""

    # True when update() treats every element independently, making the method safe
    # for the flat-sharded (ZeRO-1) DistriOptimizer layout where shards cut across
    # layer boundaries. Layer-structure-aware methods (LARS) must set this False.
    elementwise = True

    def __init__(self):
        self.state: Dict[str, Any] = {"epoch": 1, "neval": 1}
        self.learningrate: float = 1e-3
        self.learningrate_decay: float = 0.0
        self.schedule: Optional[LearningRateSchedule] = None
        # True when the runtime applies weight decay BEFORE calling update()
        # (the flat-sharded DistriOptimizer path, where param names are gone
        # and the decay-exclusion mask must be applied on the flat vector —
        # see parallel/distri_optimizer._make_sharded_step). Methods with a
        # built-in decay term must skip it when this is set.
        self.external_weight_decay = False

    # ---- host side -------------------------------------------------------
    def get_learning_rate(self) -> float:
        sched = self.schedule if self.schedule is not None else Default()
        return float(sched.update(self, self.state))

    def update_state(self, **kv) -> None:
        self.state.update(kv)

    # ---- device side (pure, jittable) -----------------------------------
    def init_slots(self, params):
        return {}

    def update(self, grads, params, slots, lr, step):
        """Return (new_params, new_slots). ``lr``/``step`` are traced scalars."""
        raise NotImplementedError

    def update_flat(self, gvec, pvec, slot_vecs, lr, step, *,
                    wd_coeff=None, lr_scale=None):
        """Single fused segment-wise update over a flat f32 parameter vector.

        The flat-parameter hot path (the ZeRO-1 sharded ``DistriOptimizer``
        step) carries ONE padded f32 vector per state tensor instead of a per-leaf tree; this entry point
        collapses the N-leaf ``update`` chains into one elementwise pass over
        that vector. Per-segment hyperparameters arrive as per-ELEMENT
        coefficient vectors precomputed once by
        :meth:`~bigdl_tpu.parallel.parameter.FlatParameter.coefficient_vector`:

        * ``wd_coeff`` — per-element weight-decay coefficient (0 on excluded
          segments and the padding tail). When given, the decay term
          ``g + wd_coeff * p`` is applied HERE (post-clip, pre-momentum — the
          same placement as SGD's built-in term) and the method's own decay is
          disabled for the call via ``external_weight_decay``. When None, the
          method's built-in uniform decay applies as usual — but a method with
          path-based exclusions REQUIRES the coefficient vector, since leaf
          paths no longer exist on the flat layout.
        * ``lr_scale`` — per-element LR multiplier (layer-wise LR recipes);
          every shipped elementwise rule broadcasts a vector LR exactly like
          the scalar.

        Works generically for every elementwise method (the per-leaf rules are
        pure ``tree_map``s, and a bare vector is a one-leaf tree); methods
        with ``elementwise = False`` (LARS/LAMB per-leaf norms) refuse.
        """
        if not self.elementwise:
            raise NotImplementedError(
                f"{type(self).__name__} is layer-structure-aware "
                "(elementwise=False) and has no flat-vector update"
            )
        if (
            wd_coeff is None
            and float(getattr(self, "weightdecay", 0.0) or 0.0) > 0
            and getattr(self, "weightdecay_exclude", ())
        ):
            raise ValueError(
                f"{type(self).__name__} has weightdecay_exclude patterns; the "
                "flat layout carries no parameter paths, so the caller must "
                "precompute the exclusions into a wd_coeff vector "
                "(FlatParameter.coefficient_vector)"
            )
        if lr_scale is not None:
            lr = lr * lr_scale
        if wd_coeff is None:
            return self.update(gvec, pvec, slot_vecs, lr, step)
        gvec = gvec + wd_coeff * pvec
        # the flag only matters while TRACING this update call — restore it so
        # the same method object can later drive a tree-layout optimizer
        prev = self.external_weight_decay
        self.external_weight_decay = True
        try:
            return self.update(gvec, pvec, slot_vecs, lr, step)
        finally:
            self.external_weight_decay = prev

    # ---- eager convenience mirroring reference optimize(feval, x) --------
    def optimize(self, feval, params):
        """Single eager step: feval(params) -> (loss, grads). Returns (params, loss)."""
        loss, grads = feval(params)
        if not hasattr(self, "_slots"):
            self._slots = self.init_slots(params)
        lr = self.get_learning_rate()
        params, self._slots = self.update(
            grads, params, self._slots, jnp.asarray(lr), jnp.asarray(self.state["neval"])
        )
        self.state["neval"] += 1
        return params, loss


def _wd_excluded(path, patterns) -> bool:
    """THE weight-decay exclusion convention: substring match against the
    leaf's pytree path — one definition shared by every method that
    honors ``weightdecay_exclude`` (SGD, Lamb) so they can't diverge."""
    import jax.tree_util as jtu

    s = jtu.keystr(path)
    return any(pat in s for pat in patterns)


class SGD(OptimMethod):
    """SGD with momentum/dampening/nesterov/weightDecay + LR schedules
    (reference: $DL/optim/SGD.scala).

    ``weightdecay_exclude``: substring patterns matched against each param's
    pytree path (e.g. ``("_bn", "bias")``) that skip weight decay — the
    ImageNet recipe's "no decay on BatchNorm γ/β and biases" exclusions,
    which the reference encodes per-model via its optnet/training scripts.
    """

    def __init__(
        self,
        learningrate: float = 1e-3,
        learningrate_decay: float = 0.0,
        weightdecay: float = 0.0,
        momentum: float = 0.0,
        dampening: Optional[float] = None,
        nesterov: bool = False,
        leaningrate_schedule: Optional[LearningRateSchedule] = None,
        weightdecay_exclude: Optional[Sequence[str]] = None,
    ):
        super().__init__()
        self.learningrate = learningrate
        self.learningrate_decay = learningrate_decay
        self.weightdecay = weightdecay
        self.momentum = momentum
        self.dampening = dampening if dampening is not None else momentum
        self.nesterov = nesterov
        # (sic) "leaningrate" matches the reference's public param name
        self.schedule = leaningrate_schedule
        self.weightdecay_exclude = (
            tuple(weightdecay_exclude) if weightdecay_exclude else ()
        )
        if nesterov and (momentum <= 0 or self.dampening != 0):
            raise ValueError("nesterov requires momentum > 0 and dampening = 0")

    def init_slots(self, params):
        if self.momentum > 0:
            return {"velocity": _tm(jnp.zeros_like, params)}
        return {}

    def _apply_weight_decay(self, grads, params):
        wd = self.weightdecay
        if not self.weightdecay_exclude:
            return _tm(lambda g, p: g + wd * p, grads, params)
        # paths are static at trace time, so the exclusion choice compiles away
        import jax.tree_util as jtu

        def leaf(path, g, p):
            if _wd_excluded(path, self.weightdecay_exclude):
                return g
            return g + wd * p

        return jtu.tree_map_with_path(leaf, grads, params)

    def update(self, grads, params, slots, lr, step):
        wd, mom, damp = self.weightdecay, self.momentum, self.dampening
        if wd > 0 and not self.external_weight_decay:
            grads = self._apply_weight_decay(grads, params)
        if mom > 0:
            v = _tm(lambda v, g: mom * v + (1 - damp) * g, slots["velocity"], grads)
            if self.nesterov:
                grads = _tm(lambda g, vv: g + mom * vv, grads, v)
            else:
                grads = v
            slots = {"velocity": v}
        params = _tm(lambda p, g: p - lr * g, params, grads)
        return params, slots


class Adam(OptimMethod):
    """Adam (reference: $DL/optim/Adam.scala)."""

    def __init__(
        self,
        learningrate: float = 1e-3,
        learningrate_decay: float = 0.0,
        beta1: float = 0.9,
        beta2: float = 0.999,
        epsilon: float = 1e-8,
    ):
        super().__init__()
        self.learningrate = learningrate
        self.learningrate_decay = learningrate_decay
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def init_slots(self, params):
        return {"m": _tm(jnp.zeros_like, params), "v": _tm(jnp.zeros_like, params)}

    def update(self, grads, params, slots, lr, step):
        b1, b2, eps = self.beta1, self.beta2, self.epsilon
        t = step.astype(jnp.float32)
        m = _tm(lambda m, g: b1 * m + (1 - b1) * g, slots["m"], grads)
        v = _tm(lambda v, g: b2 * v + (1 - b2) * g * g, slots["v"], grads)
        bias1 = 1 - b1**t
        bias2 = 1 - b2**t
        params = _tm(
            lambda p, mm, vv: p - lr * (mm / bias1) / (jnp.sqrt(vv / bias2) + eps),
            params,
            m,
            v,
        )
        return params, {"m": m, "v": v}


class ParallelAdam(Adam):
    """Reference's ``ParallelAdam`` (``$DL/optim/ParallelAdam.scala``) shards the
    flat parameter vector across ``Engine.coreNumber`` threads and runs the Adam
    update per-slice in parallel. That exact semantic — each worker updating only
    its owned slice of the flat parameter — is what ``DistriOptimizer`` already
    does for EVERY optim method here: ``parallel/distri_optimizer.py`` runs the
    update on the ZeRO-1 shard inside ``shard_map`` (psum_scatter → per-device
    slice update → all_gather). So the parallelism lives in the runtime, not the
    method; this alias exists so reference configs naming ``ParallelAdam``
    construct without edits, and its math is identical to :class:`Adam`.
    """


class Adagrad(OptimMethod):
    def __init__(
        self,
        learningrate: float = 1e-3,
        learningrate_decay: float = 0.0,
        weightdecay: float = 0.0,
    ):
        super().__init__()
        self.learningrate = learningrate
        self.learningrate_decay = learningrate_decay
        self.weightdecay = weightdecay

    def init_slots(self, params):
        return {"accum": _tm(jnp.zeros_like, params)}

    def update(self, grads, params, slots, lr, step):
        # honor external_weight_decay like SGD: on the flat path the runtime
        # applies the decay term itself (per-segment coefficients)
        if self.weightdecay > 0 and not self.external_weight_decay:
            grads = _tm(lambda g, p: g + self.weightdecay * p, grads, params)
        accum = _tm(lambda a, g: a + g * g, slots["accum"], grads)
        params = _tm(
            lambda p, g, a: p - lr * g / (jnp.sqrt(a) + 1e-10), params, grads, accum
        )
        return params, {"accum": accum}


class Adadelta(OptimMethod):
    """decayRate=rho; reference: $DL/optim/Adadelta.scala."""

    def __init__(self, decayrate: float = 0.9, epsilon: float = 1e-10):
        super().__init__()
        self.learningrate = 1.0  # adadelta is lr-free; slot ratio sets the scale
        self.rho, self.epsilon = decayrate, epsilon

    def init_slots(self, params):
        return {
            "accum": _tm(jnp.zeros_like, params),
            "delta_accum": _tm(jnp.zeros_like, params),
        }

    def update(self, grads, params, slots, lr, step):
        rho, eps = self.rho, self.epsilon
        accum = _tm(lambda a, g: rho * a + (1 - rho) * g * g, slots["accum"], grads)
        delta = _tm(
            lambda g, a, d: g * jnp.sqrt(d + eps) / jnp.sqrt(a + eps),
            grads,
            accum,
            slots["delta_accum"],
        )
        delta_accum = _tm(
            lambda d, dd: rho * d + (1 - rho) * dd * dd, slots["delta_accum"], delta
        )
        params = _tm(lambda p, d: p - lr * d, params, delta)
        return params, {"accum": accum, "delta_accum": delta_accum}


class Adamax(OptimMethod):
    def __init__(self, learningrate: float = 2e-3, beta1: float = 0.9, beta2: float = 0.999,
                 epsilon: float = 1e-38):
        super().__init__()
        self.learningrate = learningrate
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def init_slots(self, params):
        return {"m": _tm(jnp.zeros_like, params), "u": _tm(jnp.zeros_like, params)}

    def update(self, grads, params, slots, lr, step):
        b1, b2 = self.beta1, self.beta2
        t = step.astype(jnp.float32)
        m = _tm(lambda m, g: b1 * m + (1 - b1) * g, slots["m"], grads)
        u = _tm(lambda u, g: jnp.maximum(b2 * u, jnp.abs(g) + self.epsilon), slots["u"], grads)
        params = _tm(
            lambda p, mm, uu: p - (lr / (1 - b1**t)) * mm / uu, params, m, u
        )
        return params, {"m": m, "u": u}


class RMSprop(OptimMethod):
    def __init__(self, learningrate: float = 1e-2, learningrate_decay: float = 0.0,
                 decayrate: float = 0.99, epsilon: float = 1e-8):
        super().__init__()
        self.learningrate = learningrate
        self.learningrate_decay = learningrate_decay
        self.rho, self.epsilon = decayrate, epsilon

    def init_slots(self, params):
        return {"accum": _tm(jnp.zeros_like, params)}

    def update(self, grads, params, slots, lr, step):
        rho = self.rho
        accum = _tm(lambda a, g: rho * a + (1 - rho) * g * g, slots["accum"], grads)
        params = _tm(
            lambda p, g, a: p - lr * g / (jnp.sqrt(a) + self.epsilon), params, grads, accum
        )
        return params, {"accum": accum}


class Ftrl(OptimMethod):
    """FTRL-proximal (reference: $DL/optim/Ftrl.scala), wide&deep's sparse optimizer."""

    def __init__(
        self,
        learningrate: float = 1e-3,
        learningrate_power: float = -0.5,
        initial_accumulator_value: float = 0.1,
        l1_regularization_strength: float = 0.0,
        l2_regularization_strength: float = 0.0,
    ):
        super().__init__()
        self.learningrate = learningrate
        self.lr_power = learningrate_power
        self.init_accum = initial_accumulator_value
        self.l1 = l1_regularization_strength
        self.l2 = l2_regularization_strength

    def init_slots(self, params):
        return {
            "accum": _tm(lambda p: jnp.full_like(p, self.init_accum), params),
            "linear": _tm(jnp.zeros_like, params),
        }

    def update(self, grads, params, slots, lr, step):
        lp = self.lr_power

        def upd(p, g, a, l):
            new_a = a + g * g
            sigma = (new_a**-lp - a**-lp) / lr
            new_l = l + g - sigma * p
            quad = new_a**-lp / lr + 2 * self.l2
            pre = jnp.clip(new_l, -self.l1, self.l1) - new_l
            new_p = jnp.where(jnp.abs(new_l) > self.l1, pre / quad, 0.0)
            return new_p, new_a, new_l

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_a = treedef.flatten_up_to(slots["accum"])
        flat_l = treedef.flatten_up_to(slots["linear"])
        out = [upd(p, g, a, l) for p, g, a, l in zip(flat_p, flat_g, flat_a, flat_l)]
        params = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
        accum = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
        linear = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
        return params, {"accum": accum, "linear": linear}


class Lamb(OptimMethod):
    """LAMB (You et al. 2020) — layer-wise adaptation of Adam for
    large-batch training; the Adam-family companion to :class:`LarsSGD`
    (the reference's large-batch method, ``$DL/optim/LarsSGD.scala``).

    AdamW-style decoupled weight decay inside the update direction
    (``u = m̂/(√v̂+ε) + wd·p``), then a per-leaf trust ratio
    ``||p|| / ||u||`` rescales the step — layers with small updates
    relative to their weights take proportionally larger steps.
    ``weightdecay_exclude`` follows SGD's substring-path convention
    (no decay on BN γ/β and biases in the usual recipes).
    """

    elementwise = False  # per-leaf norms: incompatible with flat-sharded updates

    def __init__(
        self,
        learningrate: float = 1e-3,
        learningrate_decay: float = 0.0,
        beta1: float = 0.9,
        beta2: float = 0.999,
        epsilon: float = 1e-6,
        weightdecay: float = 0.0,
        weightdecay_exclude: Optional[Sequence[str]] = None,
    ):
        super().__init__()
        self.learningrate = learningrate
        self.learningrate_decay = learningrate_decay
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.weightdecay = weightdecay
        self.weightdecay_exclude = (
            tuple(weightdecay_exclude) if weightdecay_exclude else ()
        )

    def init_slots(self, params):
        return {"m": _tm(jnp.zeros_like, params), "v": _tm(jnp.zeros_like, params)}

    def update(self, grads, params, slots, lr, step):
        import jax.tree_util as jtu

        b1, b2, eps, wd = self.beta1, self.beta2, self.epsilon, self.weightdecay
        t = step.astype(jnp.float32)
        m = _tm(lambda m, g: b1 * m + (1 - b1) * g, slots["m"], grads)
        v = _tm(lambda v, g: b2 * v + (1 - b2) * g * g, slots["v"], grads)
        bias1 = 1 - b1**t
        bias2 = 1 - b2**t

        def leaf(path, p, mm, vv):
            u = (mm / bias1) / (jnp.sqrt(vv / bias2) + eps)
            if wd > 0 and not _wd_excluded(path, self.weightdecay_exclude):
                u = u + wd * p
            pn = jnp.linalg.norm(p.reshape(-1))
            un = jnp.linalg.norm(u.reshape(-1))
            ratio = jnp.where((pn > 0) & (un > 0), pn / un, 1.0)
            return p - lr * ratio * u

        params = jtu.tree_map_with_path(leaf, params, m, v)
        return params, {"m": m, "v": v}


class LarsSGD(SGD):
    """Layer-wise adaptive rate scaling (reference: $DL/optim/LarsSGD.scala).

    Trust ratio ||w||/(||g|| + wd*||w||) per parameter leaf (the reference scales
    per layer; leaves are per-layer here).
    """

    elementwise = False  # per-leaf norms: incompatible with flat-sharded updates

    def __init__(self, trust: float = 1.0, **kw):
        super().__init__(**kw)
        self.trust = trust

    def update(self, grads, params, slots, lr, step):
        def local_lr(p, g):
            pn = jnp.linalg.norm(p.reshape(-1))
            gn = jnp.linalg.norm(g.reshape(-1))
            ratio = jnp.where(
                (pn > 0) & (gn > 0),
                self.trust * pn / (gn + self.weightdecay * pn + 1e-12),
                1.0,
            )
            return g * ratio

        grads = _tm(local_lr, params, grads)
        return super().update(grads, params, slots, lr, step)
