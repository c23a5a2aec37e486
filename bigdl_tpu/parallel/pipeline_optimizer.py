"""First-class pipeline & expert parallel training paths (ROADMAP
"promote the MULTICHIP dryruns" item).

``parallel.pipeline``/``parallel.moe`` prove the GPipe microbatch schedule
and the switch-MoE ``all_to_all`` layout compile and step on 8 devices;
``nn.PipelinedBlocks``/``nn.MoE`` wrap them as modules. What was missing is
the production seam: an optimizer that owns the mesh, commits the stacked
parameter layouts, and drives the shared hot loop with every guarantee the
ZeRO-1 path earned — buffer donation on the carried state, exactly one
compile across ragged multi-epoch fits (pad+mask through the ``unreduced``
criterion seam), health/telemetry/perf/resilience wiring through
``_drive_loop``, and checkpoints bit-compatible with the single-path tree
layout.

Both optimizers here are :class:`~bigdl_tpu.parallel.hybrid.
HybridParallelOptimizer` subclasses — the GSPMD chassis is the right
substrate because the pp/ep shard_map programs sit INSIDE the jitted step:
jit reads the committed ``NamedSharding`` layouts off the arguments
(stage/expert-stacked leaves on their mesh axis, head/tail replicated,
batch on the data axis) and the ``shard_map`` in_specs pin the collective
schedule, so the optimizer update runs sharded with no spurious stage-param
all-gather (HLO-locked in tests).

Composition matrix (docs/parallelism.md):

* dp×pp — mesh ``('data', 'pipe')``; stage stacks shard over ``pipe``,
  each data shard runs its own pipeline (``pipeline_apply(batch_axis=
  'data')``), gradients reduce over ``data`` via GSPMD.
* dp×ep — mesh ``('data', 'expert')``; tokens shard over BOTH axes, the
  two ``all_to_all`` hops stay within each data row's expert group.
* flat-parameter / compressed-comms — refused with
  :class:`ParallelCompositionError`: one replicated flat master vector
  cannot carry the per-leaf ``P('pipe')``/``P('expert')`` placements the
  stacked layouts require (only a fully-replicated tree could compose,
  and then nothing would be pipeline- or expert-parallel).
"""

from __future__ import annotations

import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..obs.trace import span as obs_span
from ..utils.engine import Engine
from ..utils.random import RandomGenerator
from .hybrid import HybridParallelOptimizer, ParallelCompositionError
from .sharding import ShardingPlan

_tm = jax.tree_util.tree_map


class _StackedParallelOptimizer(HybridParallelOptimizer):
    """Shared chassis for the stacked-parameter parallelisms (pp/ep).

    Subclasses define the mesh axis the stacked leaves shard over, discover
    and bind their parallel modules, declare the batch partitioning, and
    check the batch fills the schedule grid; everything else — parameter
    commit, sharded audit, slot placement, the jitted standard step with
    donation + ``nvalid`` pad/mask, `_drive_loop` wiring, checkpoint/resume
    — is the one shared implementation."""

    _kind = "stacked-parallel"

    def __init__(self, model, dataset, criterion, mesh=None, axis="",
                 data_axis: Optional[str] = None, validate: bool = True,
                 donate: bool = True, flat_update: bool = False,
                 comms_dtype: Optional[str] = None):
        if flat_update:
            raise ParallelCompositionError(
                f"flat_update is incompatible with {self._kind} training: "
                f"the stacked leaves carry P({axis!r}) NamedShardings that "
                "one replicated flat master vector cannot represent (only a "
                "fully-replicated tree could compose, which would disable "
                "the parallelism). Use the tree-path update here, or "
                "DistriOptimizer parameter_sync='sharded' for the flat "
                "ZeRO-1 layout."
            )
        if comms_dtype is not None:
            raise ParallelCompositionError(
                f"comms_dtype={comms_dtype!r} is incompatible with "
                f"{self._kind} training: compressed gradient collectives "
                "ride the flat codec (GradCompressor over a FlatParameter), "
                f"which cannot carry the stacked P({axis!r}) leaf layout. "
                "Gradient reduction over the data axis is performed by "
                "GSPMD at full precision on this path."
            )
        super().__init__(model, dataset, criterion, mesh=mesh,
                         data_axis=data_axis or "data", validate=validate,
                         donate=donate)
        self.axis = axis
        # None = no dp composition (batch replicated / axis-sharded only);
        # self.data_axis (from the hybrid base) keeps the default name for
        # error messages, _dp_axis carries the actual opt-in
        self._dp_axis = data_axis

    # ------------------------------------------------------- subclass hooks
    def _bind_modules(self, mesh):
        """Discover the parallel modules on the BUILT model, configure them
        onto ``mesh``, and return them. Must raise when the model carries
        none (a silently-sequential 'parallel' fit is a footgun)."""
        raise NotImplementedError

    def _check_batch(self, mesh, n_rows: int) -> None:
        """Raise ValueError when the (static) global batch cannot fill the
        schedule grid."""
        raise NotImplementedError

    def _batch_pspec(self) -> P:
        """PartitionSpec for the global batch's leading dim."""
        raise NotImplementedError

    # ------------------------------------------------------------- plumbing
    def set_micro_batches(self, n: int):
        raise NotImplementedError(
            f"gradient-accumulation micro batches are not supported on the "
            f"{self._kind} path (and would be confused with the GPipe "
            "schedule's n_micro); size the global batch to the mesh instead"
        )

    def _resolve_mesh(self):
        if self._mesh is not None:
            mesh = self._mesh
        else:
            mesh = Engine.mesh() if Engine.is_initialized() else None
        if mesh is None or self.axis not in mesh.shape:
            have = tuple(mesh.shape) if mesh is not None else None
            raise ValueError(
                f"{type(self).__name__} needs a mesh carrying the "
                f"{self.axis!r} axis (have {have}); pass "
                f"mesh=make_mesh({{'{self.axis}': S}}) or include a "
                f"{self.axis!r} axis when initializing the Engine mesh"
            )
        if self._dp_axis is not None and self._dp_axis not in mesh.shape:
            raise ValueError(
                f"data_axis {self._dp_axis!r} not in mesh axes "
                f"{tuple(mesh.shape)}"
            )
        return mesh

    def _stacked_rules(self, modules):
        """Ordered (regex, PartitionSpec) rules placing each module's
        stacked leaves on ``self.axis`` (leading dim), default replicated."""
        raise NotImplementedError

    def _optimize_impl(self):
        model, method = self.model, self.optim_method
        mesh = self._resolve_mesh()

        x0 = self._first_batch_input()
        if not model.is_built():
            # global-view program, like the hybrid base: GSPMD partitions
            # the traced full-batch computation
            model.build(RandomGenerator.next_key(), jax.eval_shape(lambda: x0))
        self._audit_params()
        modules = self._bind_modules(mesh)
        self._check_batch(mesh, int(x0.shape[0]))
        self._install_health()  # hooks seed state BEFORE the pytree is read
        if self.health is not None and self._dp_axis is not None:
            # data-axis mesh localization: batch rows are contiguous blocks
            # per data shard (the data axis leads the batch partitioning),
            # so a poisoned record is blamed on its mesh coordinate
            n_data = mesh.shape[self._dp_axis]
            self._health_mesh_shards = n_data
            self.health.bind_mesh_axis(self._dp_axis, n_data)
        else:
            self._health_mesh_shards = None

        params, model_state = model.get_parameters(), model.get_state()
        self.plan = ShardingPlan(self._stacked_rules(modules))
        self.plan.validate(params, mesh)
        param_sh = self.plan.shardings(params, mesh)
        repl = NamedSharding(mesh, P())
        batch_sh = NamedSharding(mesh, self._batch_pspec())

        host_params = params  # pre-commit tree (aliasing audit needs it)
        params = jax.device_put(params, param_sh)
        if self.validate:
            from ..analysis import ShardedParamAudit

            with obs_span("sharded_param_audit"):
                ShardedParamAudit(params, aliasing_tree=host_params).check()
        model_state = _tm(
            lambda a: jax.device_put(jnp.asarray(a), repl), model_state
        )
        slots = self._init_slots(method, params)
        slots = _tm(
            lambda s: s if hasattr(s, "sharding") else jnp.asarray(s), slots
        )

        def place_batch(x, t):
            # prefetch-thread placement: overlaps the next step's compute
            with obs_span("place_batch"):
                return jax.device_put(x, batch_sh), jax.device_put(t, batch_sh)

        return self._run_with_step(
            self._cached_standard_step(method), params, model_state, slots,
            place_batch=place_batch,
        )


class PipelineOptimizer(_StackedParallelOptimizer):
    """GPipe pipeline-parallel training over a ``pipe`` mesh axis.

    Every :class:`~bigdl_tpu.nn.pipelined.PipelinedBlocks` in the model is
    bound to the mesh (``n_stages`` must equal the ``pipe`` axis size);
    its stage-stacked parameters commit to ``P('pipe')`` so each device
    holds exactly its stage's weights, head/tail layers stay replicated,
    and the jitted step runs ``pipeline_apply``'s scan schedule with
    ``lax.ppermute`` ring hops. ``data_axis`` composes dp×pp: the batch
    shards over a second mesh axis and each data shard runs its own
    pipeline over the shared stage weights.

    Args:
        mesh: mesh carrying ``pipe_axis`` (and ``data_axis`` if given);
            default ``Engine.mesh()``.
        pipe_axis: stage mesh-axis name (size S = ``n_stages``).
        data_axis: optional dp axis for dp×pp composition.
        n_micro: GPipe microbatch count override applied to every bound
            stack (default: each module's own setting, default S). The
            schedule's idle fraction (S-1)/(n_micro+S-1) is stamped on
            every perf record as ``pipe_bubble_frac``.
        flat_update / comms_dtype: refused with
            :class:`ParallelCompositionError` (see module docstring).
    """

    _kind = "pipeline-parallel"

    def __init__(self, model, dataset, criterion, mesh=None,
                 pipe_axis: str = "pipe", data_axis: Optional[str] = None,
                 n_micro: Optional[int] = None, validate: bool = True,
                 donate: bool = True, flat_update: bool = False,
                 comms_dtype: Optional[str] = None):
        super().__init__(model, dataset, criterion, mesh=mesh,
                         axis=pipe_axis, data_axis=data_axis,
                         validate=validate, donate=donate,
                         flat_update=flat_update, comms_dtype=comms_dtype)
        if n_micro is not None and n_micro < 1:
            raise ValueError(f"n_micro must be >= 1, got {n_micro}")
        self.n_micro = n_micro

    def _bind_modules(self, mesh):
        from ..nn.pipelined import PipelinedBlocks

        mods = [m for m in self.model.walk() if isinstance(m, PipelinedBlocks)]
        if not mods:
            raise ValueError(
                "PipelineOptimizer: the model carries no PipelinedBlocks — "
                "wrap the repeated stage in nn.PipelinedBlocks(stage, "
                "n_stages) (head/tail layers stay outside the stack)"
            )
        s = mesh.shape[self.axis]
        for m in mods:
            if m.n_stages != s:
                raise ValueError(
                    f"{m.name()}: n_stages={m.n_stages} != {self.axis!r} "
                    f"mesh axis size {s} — size the stack to the mesh"
                )
            if self.n_micro is not None:
                m.n_micro = self.n_micro
            m.pipeline_parallel = True
            m.mesh_axis = self.axis
            m.batch_axis = self._dp_axis
            m.set_mesh(mesh)
        # one bubble-fraction stamp per fit: the schedule is shared (the
        # n_micro override applies to every stack; otherwise modules default
        # to S)
        n_micro = self.n_micro or mods[0].n_micro or s
        self._perf.note_pipeline_schedule(s, n_micro)
        return mods

    def _check_batch(self, mesh, n_rows: int) -> None:
        s = mesh.shape[self.axis]
        dp = mesh.shape[self._dp_axis] if self._dp_axis is not None else 1
        if n_rows % dp:
            raise ValueError(
                f"global batch {n_rows} not divisible by data axis "
                f"{self._dp_axis!r} size {dp}"
            )
        n_micro = self.n_micro or s
        if (n_rows // dp) % n_micro:
            raise ValueError(
                f"per-data-shard batch {n_rows // dp} not divisible by "
                f"n_micro {n_micro} — the GPipe grid needs "
                f"batch = data({dp}) x n_micro({n_micro}) x microbatch rows"
            )

    def _batch_pspec(self) -> P:
        return P(self._dp_axis) if self._dp_axis is not None else P()

    def _stacked_rules(self, modules):
        # each stack's params live under "<module name>/stages/..." in the
        # parameter tree (containers key children by name); the stacked
        # leading dim S shards over the pipe axis, everything else replicates
        return [
            (re.escape(m.name()) + r"/stages/", P(self.axis))
            for m in modules
        ]


class ExpertParallelOptimizer(_StackedParallelOptimizer):
    """Switch/GShard expert-parallel training over an ``expert`` mesh axis.

    Every :class:`~bigdl_tpu.nn.moe.MoE` in the model is bound to the mesh
    (``n_experts`` must equal the ``expert`` axis size); its expert-stacked
    FFN leaves commit to ``P('expert')`` so each device holds one expert,
    the router stays replicated, and the jitted step runs ``moe_ffn``'s two
    ``lax.all_to_all`` dispatch hops. ``data_axis`` composes dp×ep: tokens
    shard over BOTH axes and each data row's expert group exchanges only
    its own tokens.

    Ragged-fit note: pad rows are masked out of the loss exactly (the
    ``unreduced`` seam), but they still route — budget ``capacity_factor``
    headroom, or keep epochs divisible (docs/parallelism.md).
    """

    _kind = "expert-parallel"

    def __init__(self, model, dataset, criterion, mesh=None,
                 expert_axis: str = "expert",
                 data_axis: Optional[str] = None, validate: bool = True,
                 donate: bool = True, flat_update: bool = False,
                 comms_dtype: Optional[str] = None):
        super().__init__(model, dataset, criterion, mesh=mesh,
                         axis=expert_axis, data_axis=data_axis,
                         validate=validate, donate=donate,
                         flat_update=flat_update, comms_dtype=comms_dtype)

    def _bind_modules(self, mesh):
        from ..nn.moe import MoE

        mods = [m for m in self.model.walk() if isinstance(m, MoE)]
        if not mods:
            raise ValueError(
                "ExpertParallelOptimizer: the model carries no nn.MoE — "
                "add an MoE FFN (or use a data-parallel optimizer)"
            )
        e = mesh.shape[self.axis]
        for m in mods:
            if m.n_experts != e:
                raise ValueError(
                    f"{m.name()}: n_experts={m.n_experts} != {self.axis!r} "
                    f"mesh axis size {e} — size the layer to the mesh"
                )
            m.expert_parallel = True
            m.mesh_axis = self.axis
            m.batch_axis = self._dp_axis
            m.set_mesh(mesh)
        return mods

    def _check_batch(self, mesh, n_rows: int) -> None:
        e = mesh.shape[self.axis]
        dp = mesh.shape[self._dp_axis] if self._dp_axis is not None else 1
        if n_rows % (dp * e):
            raise ValueError(
                f"global batch {n_rows} not divisible by "
                f"data({dp}) x experts({e}) = {dp * e} — the token shards "
                "must tile the mesh"
            )

    def _batch_pspec(self) -> P:
        if self._dp_axis is not None:
            # tokens shard over BOTH axes: non-MoE layers run data-parallel
            # across all devices, and the MoE shard_map's all_to_all stays
            # within each data row's expert group
            return P((self._dp_axis, self.axis))
        return P(self.axis)

    def _stacked_rules(self, modules):
        # expert-stacked FFN leaves (leading dim E) shard over the expert
        # axis; the router (and every non-MoE layer) stays replicated
        return [
            (re.escape(m.name()) + r"/(w1|b1|w2|b2)$", P(self.axis))
            for m in modules
        ]
