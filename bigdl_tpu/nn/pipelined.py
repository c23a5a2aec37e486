"""Pipelined block stack — the framework-surface wrapper over
``parallel.pipeline.pipeline_apply`` (VERDICT r4 next #3).

Beyond-reference capability (the reference scales only via data
parallelism; SURVEY.md §2.5): S repetitions of one stage module — the
transformer-block-stack shape — exposed as an ``AbstractModule`` so
pipeline parallelism drives through the ordinary Module/Optimizer UX:
serializable, usable inside ``Sequential``, trainable with
``LocalOptimizer``.

Two execution paths with identical math (tested against each other):

* sequential (default): ``lax.scan`` over the stage-stacked params — the
  single-device formulation XLA unrolls efficiently.
* pipeline-parallel: ``pipeline_apply``'s GPipe microbatch schedule over a
  ``pipe`` mesh axis, engaged when ``pipeline_parallel=True`` and a mesh
  carrying ``mesh_axis`` is available (``Engine.init(mesh_axis_name=
  'pipe')`` or ``set_mesh``). ``batch_axis`` composes dp×pp: the batch dim
  shards over a second mesh axis while stage weights shard over ``axis``.

Constraints (the identical-stage GPipe formulation): the stage must map
``spec -> same spec`` (reshaping head/tail layers go outside the stack)
and must be stateless (no BN running stats; layer-norm is the
transformer-native choice anyway).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .module import AbstractModule, run_child

_tm = jax.tree_util.tree_map


class PipelinedBlocks(AbstractModule):
    """``x -> stage^S(x)``: S independently-initialized copies of ``stage``.

    Args:
        stage: template module; its params are re-initialized per stage
            (stacked with leading dim S, the layout ``pipeline_apply``
            shards over the ``pipe`` mesh axis).
        n_stages: repetition count S (= the ``pipe`` mesh-axis size when
            pipeline-parallel).
        n_micro: GPipe microbatch count (pipeline path only; divides the
            per-dp-shard batch; default S).
        pipeline_parallel: opt into the sharded schedule when a ``pipe``
            mesh axis is available.
        mesh_axis / batch_axis: mesh axis names for pp and (optionally)
            the composed dp dimension.
        remat_stages: checkpoint each stage call (``jax.checkpoint``) —
            the backward recomputes intra-stage activations instead of
            stashing them per schedule tick, trading FLOPs for most of
            1F1B's activation-memory benefit; outputs and gradients stay
            bit-identical. Applies to both execution paths.
    """

    def __init__(self, stage: AbstractModule, n_stages: int,
                 n_micro: Optional[int] = None,
                 pipeline_parallel: bool = False, mesh_axis: str = "pipe",
                 batch_axis: Optional[str] = None,
                 remat_stages: bool = False):
        super().__init__()
        if not isinstance(stage, AbstractModule):
            raise TypeError(f"stage must be a module, got {type(stage)}")
        if n_stages < 2:
            raise ValueError(f"n_stages must be >= 2, got {n_stages}")
        self.stage = stage
        self.n_stages = n_stages
        self.n_micro = n_micro
        self.pipeline_parallel = pipeline_parallel
        self.mesh_axis = mesh_axis
        self.batch_axis = batch_axis
        # checkpoint each stage call: backward recomputes intra-stage
        # activations instead of stashing them per schedule tick — most of
        # 1F1B's activation-memory benefit under the static GPipe schedule
        # (bit-identical outputs/grads). Applies to the sequential
        # fallback too, so both paths keep identical autodiff behavior.
        self.remat_stages = remat_stages
        self._mesh = None  # runtime-injected; never serialized

    # ------------------------------------------------------------------ mesh
    def set_mesh(self, mesh) -> "PipelinedBlocks":
        """Inject the device mesh for the pipeline path (runtime state, not
        topology — not serialized)."""
        self._mesh = mesh
        return self

    def _fits_grid(self, mesh, batch: int) -> bool:
        """Does this (static) batch fill the dp x microbatch grid?"""
        n_micro = self.n_micro or mesh.shape[self.mesh_axis]
        if self.batch_axis is not None and self.batch_axis in mesh.shape:
            dp = mesh.shape[self.batch_axis]
            return batch % dp == 0 and (batch // dp) % n_micro == 0
        return batch % n_micro == 0

    def _resolve_mesh(self):
        if self._mesh is not None:
            return self._mesh
        from ..utils.engine import Engine

        if Engine.is_initialized():
            mesh = Engine.mesh()
            if mesh is not None and self.mesh_axis in mesh.shape:
                return mesh
        return None

    # ----------------------------------------------------------------- build
    def build(self, rng, in_spec):
        # build the template S times, harvesting one param set per stage —
        # independent initializations, identical structure
        per_stage = []
        for i in range(self.n_stages):
            out_spec = self.stage.build(jax.random.fold_in(rng, i), in_spec)
            state = self.stage.get_state()
            if jax.tree_util.tree_leaves(state):
                raise ValueError(
                    f"{self.name()}: stage carries mutable state (running "
                    "stats, or an auxiliary loss the schedule could not "
                    "collect) — pipeline stages must be stateless. For "
                    "nn.MoE stages pass aux_loss_coeff=0.")
            # leafless but structured (container state dicts) — what the
            # stage's _apply expects to be handed back
            self._stage_state = state
            per_stage.append(self.stage.get_parameters())
        flat_in = jax.tree_util.tree_structure(in_spec)
        flat_out = jax.tree_util.tree_structure(out_spec)
        in_leaves = jax.tree_util.tree_leaves(in_spec)
        out_leaves = jax.tree_util.tree_leaves(out_spec)
        same = flat_in == flat_out and all(
            a.shape == b.shape and a.dtype == b.dtype
            for a, b in zip(in_leaves, out_leaves))
        if not same:
            raise ValueError(
                f"{self.name()}: stage maps {in_spec} -> {out_spec}; the "
                "pipelined stack needs a shape-preserving stage (put "
                "reshaping head/tail layers outside)")
        self._params = {"stages": _tm(lambda *ls: jnp.stack(ls), *per_stage)}
        self._state = {}
        self._grads = _tm(jnp.zeros_like, self._params)
        self._built = True
        return out_spec

    def _build(self, rng, in_spec):  # pragma: no cover - build() overridden
        raise AssertionError("PipelinedBlocks overrides build()")

    # ----------------------------------------------------------------- apply
    def _apply(self, params, state, x, training, rng):
        x = jnp.asarray(x)
        stacked = params["stages"]

        def stage_fn(p_one, h):
            y, _ = run_child(self.stage, p_one, self._stage_state, h,
                             training, rng)
            return y

        if self.remat_stages:
            # prevent_cse=False: the wrapped fn only runs inside lax.scan
            # bodies, where CSE prevention is unnecessary (jax.checkpoint
            # docs) and its optimization barriers just block XLA fusion
            stage_fn = jax.checkpoint(stage_fn, prevent_cse=False)

        mesh = self._resolve_mesh() if self.pipeline_parallel else None
        if mesh is not None and not self._fits_grid(mesh, x.shape[0]):
            # a batch that doesn't fill the microbatch grid (one inference
            # probe row, a ragged final batch) falls back to the sequential
            # path — identical math, parity-tested — instead of forcing
            # every caller to hand-toggle pipeline_parallel
            mesh = None
        if mesh is not None:
            from ..parallel.pipeline import pipeline_apply

            y = pipeline_apply(stage_fn, stacked, x, mesh,
                               axis=self.mesh_axis, n_micro=self.n_micro,
                               batch_axis=self.batch_axis)
        else:
            def body(h, p_one):
                return stage_fn(p_one, h), None

            y, _ = lax.scan(body, x, stacked)
        return y, state
