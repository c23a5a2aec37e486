"""Hybrid data x tensor parallel training via GSPMD (pjit) sharding annotations.

Where :class:`~bigdl_tpu.parallel.distri_optimizer.DistriOptimizer` hand-writes
the data-parallel collective schedule with ``shard_map`` (mirroring the
reference's AllReduceParameter slice protocol, SURVEY.md §2.5), this optimizer
takes the other idiomatic TPU path — the scaling-book recipe: build an N-D
``Mesh`` (e.g. ``('data', 'model')``), annotate the batch with
``P('data', ...)`` and each parameter with its :class:`ShardingPlan` spec, jit
ONE global-view train step, and let XLA partition every matmul and insert the
ICI collectives (all-gather for column-parallel activations, psum for
row-parallel outputs, reduce-scatter for gradient averaging).

The reference has no tensor parallelism at all (§2.5 "parallelism strategy
inventory: data parallelism only") — this is the capability extension that
makes models-larger-than-one-chip trainable, composing with the same
Optimizer/Trigger/validation orchestration.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..dataset.dataset import AbstractDataSet
from ..nn.criterion import AbstractCriterion
from ..nn.module import AbstractModule
from ..obs.trace import span as obs_span
from ..optim.local_optimizer import Optimizer
from ..utils.engine import Engine
from ..utils.random import RandomGenerator
from .sharding import ShardingPlan

_tm = jax.tree_util.tree_map


class ParallelCompositionError(ValueError):
    """A requested parallelism composition the parameter layouts cannot
    carry (e.g. a compressed flat gradient exchange under per-leaf
    ``NamedSharding`` placements). Raised at construction — loudly, before
    any compile — with the reason and the supported alternative."""


def make_mesh(axis_sizes: dict, devices: Optional[Sequence] = None) -> Mesh:
    """Build an N-D mesh from ``{'data': 2, 'model': 4}``-style axis sizes.

    Axis order follows dict order; ICI-adjacent axes should be innermost
    (put 'model' last so tensor-parallel collectives ride the fastest links).
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    names = tuple(axis_sizes)
    shape = tuple(axis_sizes[n] for n in names)
    total = int(np.prod(shape))
    if total != len(devs):
        raise ValueError(f"mesh {axis_sizes} needs {total} devices, have {len(devs)}")
    return Mesh(np.array(devs).reshape(shape), names)


class HybridParallelOptimizer(Optimizer):
    """Data x tensor parallel pjit training step over a multi-axis mesh."""

    def __init__(
        self,
        model: AbstractModule,
        dataset: AbstractDataSet,
        criterion: AbstractCriterion,
        plan: Optional[ShardingPlan] = None,
        mesh: Optional[Mesh] = None,
        data_axis: str = "data",
        validate: bool = True,
        donate: bool = True,
    ):
        super().__init__(model, dataset, criterion, validate=validate,
                         donate=donate)
        self.plan = plan or ShardingPlan()
        self._mesh = mesh
        self.data_axis = data_axis

    def _perf_device_count(self) -> int:
        # the pjit step spans every device of the (possibly N-D) mesh: the
        # MFU denominator counts them all
        return int(self._resolve_mesh().devices.size)

    def _supports_elastic(self) -> bool:
        return True

    def _resolve_mesh(self) -> Mesh:
        base = self._mesh
        if base is None:
            base = Engine.mesh()
            if self.data_axis not in base.axis_names:
                raise ValueError(
                    f"Engine mesh axes {base.axis_names} lack data axis "
                    f"{self.data_axis!r}; pass mesh= explicitly or Engine.init(...)"
                )
        el = self._elastic
        if el is not None:
            # elastic view: only the (leading) data axis shrinks/re-expands;
            # the jitted global-view step retraces once per mesh shape via
            # jit's own cache — still one compile per mesh configuration
            return el.hybrid_mesh(base, self.data_axis)
        return base

    def _optimize_impl(self) -> AbstractModule:
        model, method = self.model, self.optim_method
        mesh = self._resolve_mesh()
        n_data = mesh.shape[self.data_axis]

        x0 = self._first_batch_input()
        if x0.shape[0] % n_data:
            raise ValueError(
                f"global batch {x0.shape[0]} not divisible by data axis {n_data}"
            )
        if not model.is_built():
            # global-view program: build from the FULL batch spec (GSPMD
            # partitions the traced computation; contrast shard_map in
            # distri_optimizer which traces the per-device program)
            model.build(RandomGenerator.next_key(), jax.eval_shape(lambda: x0))
        self._install_health()  # hooks seed state BEFORE the pytree is read
        # mesh localization (the "poisoned mesh axis" health satellite): the
        # jitted step additionally counts non-finite input/target values PER
        # DATA SHARD (contiguous row blocks of the global batch = the data
        # axis placement), so a poisoned record is blamed on its mesh
        # coordinate in the health record and the divergence rollback
        if self.health is not None:
            self._health_mesh_shards = n_data
            self.health.bind_mesh_axis(self.data_axis, n_data)
        else:
            self._health_mesh_shards = None
        params, model_state = model.get_parameters(), model.get_state()
        self.plan.validate(params, mesh)

        param_sh = self.plan.shardings(params, mesh)
        repl = NamedSharding(mesh, P())
        batch_sh = NamedSharding(mesh, P(self.data_axis))

        # commit placements; jit reads shardings off the args and GSPMD
        # propagates them through the whole step (grads/slots inherit the
        # parameter layout, so optimizer state is TP-sharded for free)
        host_params = params  # pre-commit tree: id()-aliasing is only
        params = jax.device_put(params, param_sh)  # meaningful before this
        if self.validate:
            # per-shard hygiene on the COMMITTED GSPMD layout (the closing
            # slice of the ROADMAP sharded-audit item): finiteness checked on
            # the addressable shards the devices actually hold, aliasing on
            # the PRE-commit host tree (device_put severs leaf identity, so
            # two tied host leaves silently fork into independent copies —
            # exactly what the audit must flag before donation trains them)
            from ..analysis import ShardedParamAudit

            with obs_span("sharded_param_audit"):
                ShardedParamAudit(params, aliasing_tree=host_params).check()
        model_state = _tm(lambda a: jax.device_put(jnp.asarray(a), repl), model_state)
        slots = self._init_slots(method, params)
        slots = _tm(lambda s: s if hasattr(s, "sharding") else jnp.asarray(s), slots)

        def place_batch(x, t):
            # runs inside the prefetch thread; the span makes the GSPMD batch
            # placement cost visible next to prefetch/dispatch in telemetry
            with obs_span("place_batch"):
                return jax.device_put(x, batch_sh), jax.device_put(t, batch_sh)

        return self._run_with_step(
            self._cached_standard_step(method), params, model_state, slots,
            place_batch=place_batch,
        )
