"""DistriOptimizer — synchronous data-parallel training over a device mesh.

Reference behavior (SURVEY.md §3.1): ``$DL/optim/DistriOptimizer.scala`` runs one
Spark job per iteration: executors fetch weight slices from the BlockManager,
run multi-threaded local forward/backward, put fp16-compressed gradient slices,
reduce their owned slice, apply the sharded optimizer update, and publish the
updated slice. Gradient-drop straggler mitigation skips the slowest p% of
sub-models.

TPU-native design — the architectural centerpiece of this framework:

* The whole iteration is ONE jitted SPMD program over ``Mesh(devices, ('data',))``
  via ``jax.shard_map``: batch sharded on 'data' (partition↔device 1:1, the
  north-star mapping), params replicated.
* ``parameter_sync='sharded'`` (default) mirrors AllReduceParameter exactly:
  ``psum_scatter`` the flat gradient → optimizer update on the owned slice only
  (optimizer slots live sharded, ZeRO-1 placement) → ``all_gather`` updated
  weights. ``'replicated'`` does plain ``pmean`` + replicated update (cheaper
  for small models).
* No gradient drop: under SPMD there are no stragglers — every device executes
  the same program in lockstep on identical hardware.
* BN running stats are cross-replica averaged each step (the reference keeps
  them per-replica as an artifact of its executor model; averaging is the
  SPMD-correct equivalent and is documented as a deliberate deviation).
* Per-device RNG streams derive from the step key via ``fold_in(axis_index)``,
  so dropout masks differ across the batch shards as they do across executors.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..dataset.dataset import AbstractDataSet
from ..nn.criterion import AbstractCriterion
from ..nn.module import AbstractModule
from ..obs.trace import span as obs_span
from ..optim.local_optimizer import (
    Optimizer, _to_device_tree, step_program_name)
from ..utils.engine import Engine
from ..utils.random import RandomGenerator
from .parameter import FlatParameter

log = logging.getLogger("bigdl_tpu.parallel")

_tm = jax.tree_util.tree_map


class DistriOptimizer(Optimizer):
    def __init__(
        self,
        model: AbstractModule,
        dataset: AbstractDataSet,
        criterion: AbstractCriterion,
        parameter_sync: str = "sharded",
        gradient_dtype=None,
        validate: bool = True,
        donate: bool = True,
        flat_update: bool = False,
        async_placement: bool = True,
        comms_dtype=None,
        error_feedback: bool = True,
        master_dtype=None,
        slot_dtype=None,
    ):
        # flat_update only affects the REPLICATED sync mode (flat master
        # vector + one fused pmean/update instead of per-leaf trees); the
        # sharded ZeRO-1 mode always carries the flat master state — that
        # layout IS the AllReduceParameter design. comms_dtype/master_dtype/
        # slot_dtype are the flat path's low-precision policy
        # (docs/performance.md): compressed gradient collectives with error
        # feedback + quantized training state.
        super().__init__(model, dataset, criterion, validate=validate,
                         donate=donate, flat_update=flat_update,
                         comms_dtype=comms_dtype,
                         error_feedback=error_feedback,
                         master_dtype=master_dtype, slot_dtype=slot_dtype)
        if parameter_sync not in ("auto", "sharded", "replicated"):
            raise ValueError(f"unknown parameter_sync {parameter_sync!r}")
        self.parameter_sync = parameter_sync
        # bf16 gradient wire format = the fp16 CompressedTensor analog;
        # superseded by comms_dtype (which adds per-segment scales + error
        # feedback) when both are set
        self.gradient_dtype = gradient_dtype
        # async_placement=True (default) runs the batch's sharding commit —
        # the host→device transfer — inside the PREFETCH worker, so it
        # overlaps the running step's compute; False restores the serialized
        # behavior (commit on the consumer thread, in front of every SPMD
        # dispatch) — kept as the measurable baseline for the dispatch-gap
        # span-overlap tests (docs/performance.md).
        self.async_placement = bool(async_placement)
        # per-mesh-configuration step cache: device-id tuple → (method,
        # sync, FlatParameter, jitted step, health, mesh). Reused across
        # retry attempts (a resume re-commits shardings and dispatches into
        # the SAME compiled SPMD program — zero recompiles,
        # docs/resilience.md) AND across elastic remeshes: a rejoin back to
        # a previously-seen mesh reuses its compiled step, so training pays
        # exactly one compile per mesh configuration
        self._distri_step_cache = {}

    def set_micro_batches(self, n: int) -> "DistriOptimizer":
        """Not supported here: the SPMD steps are built by
        _make_sharded_step/_make_replicated_step, which don't read the
        setting — silently dropping the documented HBM lever would leave
        a user OOMing with no indication why (r5 review finding). Under
        dp sharding the per-chip batch is already batch/n_dev; to cut
        activation memory further use ``nn.Remat`` on the model."""
        raise NotImplementedError(
            "set_micro_batches is LocalOptimizer-only; with DistriOptimizer "
            "use nn.Remat (gradient checkpointing) for activation memory")

    # ------------------------------------------------------------ clipping
    def _clip_shard_global(self, g_shard, axis):
        """Clip the AGGREGATED gradient using its global norm (psum of shard
        norms) — clipping local grads pre-aggregation would diverge from
        LocalOptimizer semantics (clip(mean g) != mean(clip g))."""
        if self._grad_clip_const is not None:
            lo, hi = self._grad_clip_const
            g_shard = jnp.clip(g_shard, lo, hi)
        if self._grad_clip_norm is not None:
            gnorm = jnp.sqrt(jax.lax.psum(jnp.sum(g_shard * g_shard), axis))
            scale = jnp.minimum(1.0, self._grad_clip_norm / (gnorm + 1e-12))
            g_shard = g_shard * scale
        return g_shard

    @staticmethod
    def _state_sync(new_ms, loss, axis):
        """The step's other collective: the model state (batch-norm running
        statistics, counters) and the loss averaged over the replicas, under
        the step-part scope ``state_sync`` (beside ``grad_exchange``, which
        owns the gradient's)."""
        with jax.named_scope("state_sync"):
            return (_tm(lambda a: jax.lax.pmean(a, axis), new_ms),
                    jax.lax.pmean(loss, axis))

    def _ragged_seam_policy(self) -> str:
        # the SPMD steps take no nvalid scalar: a padded row would train as
        # real data. DistributedDataSet already drops non-divisible train
        # batches, so pass the rest through untouched.
        return "pass"

    def _perf_device_count(self) -> int:
        # one SPMD step spans the whole data mesh (the elastic view of it
        # when a fleet coordinator is attached): MFU divides by its size
        return int(self._training_mesh().devices.size)

    def _supports_elastic(self) -> bool:
        # resharding rides the flat master layout; _optimize_impl rejects
        # a non-flat parameter_sync when elastic is attached
        return True

    @staticmethod
    def _mesh_key(mesh) -> tuple:
        """Step-cache key: the exact device population of the mesh (shrunk
        and full meshes over the same hardware differ; a rejoin back to a
        prior population hits the cache)."""
        return tuple(int(d.id) for d in np.asarray(mesh.devices).flat)

    # ------------------------------------------------------------------ steps
    def _resolve_parameter_sync(self, method, params) -> str:
        """The ONE owner of the ``parameter_sync='auto'`` heuristic (both the
        training path and ``obs.profiler.profile_optimizer`` call this, so
        the profiler's reported layout cannot drift from the runtime's
        choice): sharded pays a per-step all-gather of the full flat vector;
        for tiny models the gather latency dominates and replicated (plain
        pmean + replicated update) wins. ZeRO-1 placement starts paying for
        itself around ~1M params (slot memory + update sharding)."""
        sync = self.parameter_sync
        if sync != "auto":
            return sync
        n_params = sum(
            int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(params)
        )
        elementwise = getattr(method, "elementwise", True)
        sync = "sharded" if (n_params >= 1_000_000 and elementwise) else "replicated"
        log.info(
            "parameter_sync=auto -> %r (%d params, elementwise=%s)",
            sync, n_params, elementwise,
        )
        return sync

    def _make_sharded_step(self, fp: FlatParameter, mesh, method, n_dev: int):
        """The ZeRO-1 sharded step over the FLAT master state: the padded f32
        vector is the carried (donated) canonical weights — mirroring
        AllReduceParameter, where the flat vector IS the training state. The
        per-layer tree exists only as slice+reshape+cast VIEWS materialized
        inside the step for the forward/backward (XLA aliases them into the
        vector's buffer), the loss is differentiated w.r.t. the vector itself
        (the gradient arrives flat — no params- or grads-sized concatenate
        anywhere in the program), and the owned shard updates through ONE
        fused segment-wise ``update_flat`` pass with weight-decay exclusions
        precomputed as a per-element coefficient vector."""
        axis = mesh.axis_names[0]
        gdtype = self.gradient_dtype
        hm = self.health
        wd_coeff_full = self._wd_coefficients(method, fp)
        # low-precision policy (docs/performance.md): comp compresses the
        # gradient exchange (per-segment scales + the carried error-feedback
        # residual as an extra donated P(axis) arg), sp wraps the fused
        # shard update in decode → f32 → stochastically-rounded downcast.
        # Policy off ⇒ both None ⇒ the traced program is byte-identical to
        # the pre-policy build (test-locked).
        sp, comp = self._precision_for(fp)
        use_err = comp is not None and comp.error_feedback

        def per_device(flat_p, model_state, slot_shard, err, x, t, lr, it,
                       rng):
            rng_local = jax.random.fold_in(rng, jax.lax.axis_index(axis))
            # differentiate w.r.t. the DECODED master so gradients stay
            # full-precision whatever the storage dtype (bf16 master)
            with jax.named_scope("param_views"):
                p_full = (sp.decode_master(flat_p) if sp is not None
                          else flat_p)

            def flat_loss(fvec, ms):
                # the views' transpose is the flat gradient's assembly
                with jax.named_scope("param_views"):
                    tree = fp.unflatten(fvec)
                return self._loss_fn(tree, ms, x, t, rng_local)

            (loss, new_ms), flat_g = jax.value_and_grad(
                flat_loss, has_aux=True
            )(p_full, model_state)
            me = jax.lax.axis_index(axis)
            with jax.named_scope("grad_exchange"):
                if comp is not None:
                    # compressed exchange: quantized codes on the wire, f32
                    # accumulation, residual carried per device
                    shard_sum, new_err, qstats = comp.exchange_sharded(
                        flat_g, None if err is None else err[0], axis, n_dev,
                        me, want_stats=hm is not None,
                    )
                    g_shard = shard_sum / n_dev
                else:
                    new_err = qstats = None
                    if gdtype is not None:
                        flat_g = flat_g.astype(gdtype)
                    # reduce-scatter: each device ends with the summed slice
                    # it owns
                    g_shard = jax.lax.psum_scatter(
                        flat_g, axis, tiled=True
                    ).astype(jnp.float32) / n_dev
                g_shard = self._clip_shard_global(g_shard, axis)
            g_stat = g_shard  # post-clip effective gradient (health stats)
            with jax.named_scope("optim_update"):
                p_shard = jax.lax.dynamic_slice(
                    flat_p, (me * fp.shard_size,), (fp.shard_size,)
                )
                wd_shard = (
                    jax.lax.dynamic_slice(
                        wd_coeff_full, (me * fp.shard_size,), (fp.shard_size,)
                    )
                    if wd_coeff_full is not None
                    else None
                )
                if sp is not None:
                    p_shard, slot_shard, p_old, p_new32 = sp.apply_update(
                        method, g_shard, p_shard, slot_shard, lr, it,
                        wd_coeff=wd_shard,
                        pad_zero=lambda v: fp.zero_pad_shard(v, me),
                    )
                else:
                    p_old = p_shard  # pre-update shard (health ratio)
                    p_shard, slot_shard = method.update_flat(
                        g_shard, p_shard, slot_shard, lr, it, wd_coeff=wd_shard
                    )
                    # the padding tail must stay zero in the CARRIED master
                    # vector (e.g. Adamax's subnormal eps guard flushes to 0
                    # → 0/0 = NaN on the inert tail; donation would persist
                    # it forever)
                    p_shard = fp.zero_pad_shard(p_shard, me)
                    p_new32 = p_shard
            with jax.named_scope("param_gather"):
                new_flat = jax.lax.all_gather(p_shard, axis, tiled=True)
            new_ms, loss = self._state_sync(new_ms, loss, axis)
            outs = (new_flat, new_ms, slot_shard)
            if new_err is not None:
                outs = outs + (new_err,)
            outs = outs + (loss,)
            if hm is None:
                return outs
            # per-layer stats from this device's slice of the flat layout
            # (segment reductions against the codec geometry), psum'd so the
            # health output is replicated like the loss
            health = {
                "layers": hm.flat_shard_stats(
                    fp, g_stat, p_old, p_new32, me, axis
                )
            }
            if qstats is not None:
                health["quant"] = qstats
            acts = hm.act_stats(new_ms)
            if acts is not None:
                health["acts"] = acts
            return outs + (health,)

        if not use_err:
            body = per_device

            def per_device_noerr(flat_p, model_state, slot_shard, x, t, lr,
                                 it, rng):
                return body(flat_p, model_state, slot_shard, None, x, t, lr,
                            it, rng)

            per_device = per_device_noerr
        # donate flat/model_state/slot_shard (+ the EF residual): the
        # all-gather target aliases the carried master vector and the
        # sharded slots update in place — this is where donation pays most
        # (the framework's centerpiece path would otherwise double both
        # footprints per step)
        in_specs = (P(), P(), P(axis))
        out_specs = (P(), P(), P(axis))
        if use_err:
            in_specs = in_specs + (P(axis),)
            out_specs = out_specs + (P(axis),)
        in_specs = in_specs + (P(axis), P(axis), P(), P(), P())
        out_specs = out_specs + (P(),)
        if hm is not None:
            out_specs = out_specs + (P(),)  # replicated health pytree
        donate = (0, 1, 2, 3) if use_err else (0, 1, 2)  # EF residual too
        return jax.jit(
            shard_map(
                step_program_name(per_device),
                mesh=mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            ),
            donate_argnums=donate if self.donate else (),
        )

    def _make_replicated_flat_step(self, fp: FlatParameter, mesh, method,
                                   n_dev: int):
        """``flat_update=True`` twin of :meth:`_make_replicated_step`: the
        replicated flat master vector is the carried state, the gradient
        pmean collapses to ONE fused collective over one vector (instead of a
        per-leaf collective chain), and the update is a single segment-wise
        pass."""
        axis = mesh.axis_names[0]
        gdtype = self.gradient_dtype
        hm = self.health
        wd_coeff = self._wd_coefficients(method, fp)
        from ..optim.quantization import MASTER_SCALE_KEY

        sp, comp = self._precision_for(fp)
        use_err = comp is not None and comp.error_feedback

        def per_device(flat_p, model_state, slots, err, x, t, lr, it, rng):
            rng_local = jax.random.fold_in(rng, jax.lax.axis_index(axis))
            with jax.named_scope("param_views"):
                if sp is not None:
                    p32 = sp.decode_master(flat_p, slots.get(MASTER_SCALE_KEY))
                else:
                    p32 = flat_p

            def flat_loss(fvec, ms):
                with jax.named_scope("param_views"):
                    tree = fp.unflatten(fvec)
                return self._loss_fn(tree, ms, x, t, rng_local)

            (loss, new_ms), flat_g = jax.value_and_grad(
                flat_loss, has_aux=True
            )(p32, model_state)
            with jax.named_scope("grad_exchange"):
                if comp is not None:
                    flat_g, new_err, qstats = comp.exchange_replicated(
                        flat_g, None if err is None else err[0], axis, n_dev,
                        want_stats=hm is not None,
                    )
                else:
                    new_err = qstats = None
                    if gdtype is not None:
                        flat_g = flat_g.astype(gdtype)
                    flat_g = jax.lax.pmean(flat_g, axis).astype(jnp.float32)
                flat_g = self._clip_grads(flat_g)  # on the aggregated gradient
            with jax.named_scope("optim_update"):
                if sp is not None:
                    new_flat, slots, p_old32, p_new32 = sp.apply_update(
                        method, flat_g, flat_p, slots, lr, it,
                        wd_coeff=wd_coeff, pad_zero=fp.zero_pad, p32=p32,
                    )
                else:
                    new_flat, slots = method.update_flat(
                        flat_g, flat_p, slots, lr, it, wd_coeff=wd_coeff
                    )
                    new_flat = fp.zero_pad(new_flat)  # inert tail stays zero
                    p_old32, p_new32 = flat_p, new_flat
            new_ms, loss = self._state_sync(new_ms, loss, axis)
            outs = (new_flat, new_ms, slots)
            if new_err is not None:
                outs = outs + (new_err,)
            outs = outs + (loss,)
            if hm is None:
                return outs
            health = {"layers": hm.flat_stats(fp, flat_g, p_old32, p_new32)}
            if qstats is not None:
                health["quant"] = qstats
            acts = hm.act_stats(new_ms)
            if acts is not None:
                health["acts"] = acts
            return outs + (health,)

        if not use_err:
            body = per_device

            def per_device_noerr(flat_p, model_state, slots, x, t, lr, it,
                                 rng):
                return body(flat_p, model_state, slots, None, x, t, lr, it,
                            rng)

            per_device = per_device_noerr
        in_specs = (P(), P(), P())
        out_specs = (P(), P(), P())
        if use_err:
            in_specs = in_specs + (P(axis),)
            out_specs = out_specs + (P(axis),)
        in_specs = in_specs + (P(axis), P(axis), P(), P(), P())
        out_specs = out_specs + (P(),)
        if hm is not None:
            out_specs = out_specs + (P(),)
        donate = (0, 1, 2, 3) if use_err else (0, 1, 2)  # EF residual too
        return jax.jit(
            shard_map(
                step_program_name(per_device),
                mesh=mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            ),
            donate_argnums=donate if self.donate else (),
        )

    def _make_replicated_step(self, mesh, method, n_dev: int):
        axis = mesh.axis_names[0]
        gdtype = self.gradient_dtype
        hm = self.health

        def per_device(params, model_state, slots, x, t, lr, it, rng):
            rng_local = jax.random.fold_in(rng, jax.lax.axis_index(axis))
            (loss, new_ms), grads = jax.value_and_grad(self._loss_fn, has_aux=True)(
                params, model_state, x, t, rng_local
            )
            with jax.named_scope("grad_exchange"):
                if gdtype is not None:
                    grads = _tm(lambda g: g.astype(gdtype), grads)
                grads = _tm(
                    lambda g: jax.lax.pmean(g, axis).astype(jnp.float32), grads
                )
                grads = self._clip_grads(grads)  # on the aggregated gradient
            with jax.named_scope("optim_update"):
                new_params, slots = method.update(grads, params, slots, lr, it)
            new_ms, loss = self._state_sync(new_ms, loss, axis)
            if hm is None:
                return new_params, new_ms, slots, loss
            # replicated layout: the same tree-based stats as the local path
            # (grads are the post-pmean aggregated gradient, so every device
            # computes the identical replicated matrix)
            return new_params, new_ms, slots, loss, hm.tree_stats(
                grads, params, new_params, new_ms
            )

        out_specs = (P(), P(), P(), P())
        if hm is not None:
            out_specs = out_specs + (P(),)
        # optimize()'s driver rebinds params/ms/slots to the step outputs
        # every iteration — no reference to a donated buffer survives
        return jax.jit(
            shard_map(
                step_program_name(per_device),
                mesh=mesh,
                in_specs=(P(), P(), P(), P(axis), P(axis), P(), P(), P()),
                out_specs=out_specs,
                check_vma=False,
            ),
            donate_argnums=(0, 1, 2) if self.donate else (),
        )

    # ---------------------------------------------------------- multi-process
    @staticmethod
    def _make_batch_placer(mesh, axis):
        """Batch -> device placement for the jitted SPMD step.

        Single-controller: plain asarray (jit shards it per the in_specs).
        Multi-process (after ``Engine.init_distributed``): every process
        iterates the SAME global dataset, and each one materializes only the
        shards its addressable devices own via ``make_array_from_callback``
        — the jax analog of the reference's per-executor partition fetch
        (``$DL/optim/DistriOptimizer.scala`` executor-side batch pull,
        SURVEY.md §2.5 Engine row)."""
        if jax.process_count() == 1:
            return _to_device_tree

        def place(tree):
            def put(a):
                a = np.asarray(a)  # lint: disable=BDL005 host-side shard materialization, runs pre-dispatch
                spec = P(*((axis,) + (None,) * (a.ndim - 1)))
                sharding = jax.sharding.NamedSharding(mesh, spec)
                return jax.make_array_from_callback(
                    a.shape, sharding, lambda idx: a[idx]
                )

            return jax.tree_util.tree_map(put, tree)

        return place

    def _build_for_resume(self) -> None:
        # the traced apply sees a PER-DEVICE shard (contrast the local/pjit
        # paths, which build from the full-batch spec)
        n_dev = self._training_mesh().devices.size
        x0 = self._first_batch_input()
        spec = jax.eval_shape(lambda: x0)
        spec = jax.ShapeDtypeStruct(
            (spec.shape[0] // n_dev,) + spec.shape[1:], spec.dtype
        )
        self.model.build(RandomGenerator.next_key(), spec)

    # ---------------------------------------------------------- elastic fleet
    def _make_fleet_writer(self, fp, box, mesh):
        """The per-host-sharded checkpoint writer for an elastic run: each
        process persists only its [lo, hi) slice of the padded flat master +
        slot vectors (``shard.p<k>.<step>.npz``), and the coordinator writes
        the fleet ``manifest.<step>.json`` LAST. On the single-controller
        simulated fleet the driver holds the full vector and writes every
        shard. Low-precision storage decodes back to f32 first, so fleet
        checkpoints stay bit-compatible with unquantized runs."""
        from ..utils.serialization import (
            fleet_codec_info,
            save_fleet_checkpoint,
        )

        el = self._elastic
        sp = self._state_prec
        quantized = (
            self._precision is not None and sp is not None and sp.fp is fp
        )
        codec = fleet_codec_info(fp)
        mesh_shape = tuple(int(s) for s in np.asarray(mesh.devices).shape)

        def write(state):
            master, slots = box["state"], box["slots"]
            if quantized:
                from ..optim.quantization import MASTER_SCALE_KEY

                master = sp.decode_master(
                    master, slots.get(MASTER_SCALE_KEY)
                )
                slots = sp.decode_slots({
                    k: v for k, v in slots.items() if k != MASTER_SCALE_KEY
                })
            return save_fleet_checkpoint(
                self.checkpoint_path,
                step=int(state["neval"]),
                master=np.asarray(master),  # lint: disable=BDL005 cold checkpoint seam
                slots={k: np.asarray(v) for k, v in slots.items()},  # lint: disable=BDL005 cold checkpoint seam
                bounds=el.process_bounds(fp),
                codec=codec,
                mesh_shape=mesh_shape,
                process_count=el.n_active(),
                optim_state=dict(state),
                model_state=self.model.get_state(),
                generation=el.generation,
                keep_last=self.checkpoint_keep_last,
            )

        return write

    # --------------------------------------------------------------- optimize
    def _optimize_impl(self) -> AbstractModule:
        model, method = self.model, self.optim_method
        state = method.state
        mesh = self._training_mesh()  # elastic: the ACTIVE fleet's view
        n_dev = mesh.devices.size
        axis = mesh.axis_names[0]

        first = next(iter(self.dataset.data(train=True)), None)
        if first is None:
            raise ValueError(
                f"dataset yields no full training batch divisible by {n_dev} devices"
            )
        if first.size() % n_dev != 0:
            raise ValueError(
                f"global batch {first.size()} not divisible by {n_dev} devices"
            )
        x0 = jnp.asarray(first.get_input())
        # the traced apply sees a PER-DEVICE shard: validate and build from it
        shard_spec = jax.eval_shape(lambda: x0)
        shard_spec = jax.ShapeDtypeStruct(
            (shard_spec.shape[0] // n_dev,) + shard_spec.shape[1:], shard_spec.dtype
        )
        self._validate_before_step(shard_spec)
        if not model.is_built():
            model.build(RandomGenerator.next_key(), shard_spec)
        self._audit_params()
        self._install_health()  # hooks seed state BEFORE the pytree is read
        params, model_state = model.get_parameters(), model.get_state()

        sync = self._resolve_parameter_sync(method, params)
        # the sharded ZeRO-1 mode ALWAYS carries the flat master state (that
        # layout is the AllReduceParameter design); flat_update additionally
        # opts the replicated mode into it
        flat_mode = sync == "sharded" or self.flat_update
        if self._elastic is not None and sync != "sharded":
            raise ValueError(
                "elastic training rides the ZeRO-1 flat master layout (per-"
                "host shard bounds are FlatParameter arithmetic); use "
                "parameter_sync='sharded'"
            )
        if self._precision is not None:
            if not flat_mode:
                raise ValueError(
                    "low-precision policies (comms_dtype/master_dtype/"
                    "slot_dtype) hang off the flat master buffer; use "
                    "parameter_sync='sharded' (the ZeRO-1 flat layout) or "
                    "flat_update=True on the replicated mode"
                )
            if sync == "sharded" and self._precision.master_scaled:
                raise ValueError(
                    "master_dtype=float8 (scaled master codes) is not "
                    "supported on the ZeRO-1 sharded layout — the per-"
                    "segment scales would need a second collective per "
                    "step; use master_dtype='bfloat16' here, or the "
                    "replicated/local flat paths for the experimental fp8 "
                    "master tier"
                )
        fp = None
        if flat_mode:
            if not getattr(method, "elementwise", True):
                raise ValueError(
                    f"{type(method).__name__} is layer-structure-aware and "
                    "cannot run on the flat parameter layout; use "
                    "parameter_sync='replicated'"
                    + (" without flat_update" if sync != "sharded" else "")
                )
            fp = self._flat_codec(params, n_dev if sync == "sharded" else 1)

        hm = self.health
        mesh_key = self._mesh_key(mesh)
        cached = self._distri_step_cache.get(mesh_key)
        if cached is not None and not (
            cached[0] is method and cached[1] == sync
            and cached[2] is fp  # codec identity (stable across retries)
            and cached[4] is hm  # the step's output signature keys on health
        ):
            cached = None  # method/sync/health changed: cached step is stale
        if flat_mode:
            flatten, unflatten, slots_view = self._flat_fns(fp)
            # the ONE tree→vector copy of this run (a resume re-flattens
            # once); from here on the padded flat f32 vector is the carried,
            # donated canonical state and the tree is a per-seam VIEW
            flat = flatten(params)
            if self.validate:
                # pre-step hygiene on the EXACT flat layout the step carries:
                # dtype/finiteness per addressable shard + codec geometry —
                # and with the vector now the real master state, the aliasing
                # the audit describes is the aliasing the program runs with
                from ..analysis import FlatParamAudit

                with obs_span("flat_param_audit"):
                    FlatParamAudit(fp, flat).check()
            if hm is not None:
                hm.bind_flat(fp)  # per-layer rows = the codec's leaf geometry
                hm.bind_acts(model_state)
            slots = self._init_flat_slots(method, fp)
            entry_slots = slots  # f32 representation: what the snapshot stores
            sp, comp = self._precision_for(fp)
            use_err = comp is not None and comp.error_feedback
            if sp is not None:
                # encode ONCE at entry; the carried master/slots live in
                # storage precision from here and the cold seams decode
                # through _flat_state_thunks
                from ..optim.quantization import MASTER_SCALE_KEY

                flat, mscale = sp.encode_master(flat)
                slots = sp.encode_slots(slots)
                if mscale is not None:
                    slots = dict(slots)
                    slots[MASTER_SCALE_KEY] = mscale
            # ZeRO-1: slot vectors live sharded; replicated-flat: replicated
            slots_spec = P(axis) if sync == "sharded" else P()
            if cached is not None:
                step_fn = cached[3]
            elif sync == "sharded":
                step_fn = self._make_sharded_step(fp, mesh, method, n_dev)
            else:
                step_fn = self._make_replicated_flat_step(
                    fp, mesh, method, n_dev
                )
            carried = flat
        else:
            entry_slots = None
            use_err = False
            if hm is not None:
                hm.bind_tree(params)
                hm.bind_acts(model_state)
            slots = self._init_slots(method, params)
            slots_spec = P()
            step_fn = (cached[3] if cached is not None
                       else self._make_replicated_step(mesh, method, n_dev))
            carried = params
        self._distri_step_cache[mesh_key] = (method, sync, fp, step_fn, hm,
                                             mesh)
        self._jit_step = step_fn  # compile-count introspection (tests)

        # Commit the initial state to the STEP's output shardings before the
        # first call: otherwise call 1 (plain single-device arrays) and call 2+
        # (sharded step outputs) present different input layouts and jit
        # compiles the whole SPMD program TWICE — the time-to-first-step tax
        # this PR exists to kill.
        repl = NamedSharding(mesh, P())

        def out_sharding(spec):
            # jax hands a fully-replicated output back spelled P() — which is
            # what P(axis) IS on a one-device mesh. Committing the spelling
            # the step returns keeps call 2 on call 1's executable there too
            # (chip run, PR 21: the one-chip ZeRO-1 step compiled twice).
            sh = NamedSharding(mesh, spec)
            return repl if sh.is_fully_replicated else sh

        with obs_span("commit_shardings"):
            carried = jax.device_put(carried, repl)
            model_state = _tm(lambda a: jax.device_put(jnp.asarray(a), repl),
                              model_state)
            slots = _tm(
                lambda a: jax.device_put(
                    jnp.asarray(a),
                    out_sharding(slots_spec)
                    if getattr(jnp.asarray(a), "ndim", 0) >= 1
                    else repl,  # scalar slot state (custom methods) replicates
                ),
                slots,
            )
            if use_err:
                # the comms error-feedback residual: one padded-master-
                # geometry row per device, committed sharded on the device
                # axis and donated alongside the master vector
                box_err = jax.device_put(
                    jnp.asarray(comp.init_residual(n_dev)),
                    out_sharding(P(axis)),
                )

        # the restore contract is tree-shaped: snapshot the entry TREE (still
        # live pre-flatten) + the run's f32 slot representation (captured
        # BEFORE any low-precision encode)
        self._capture_entry_snapshot(
            params, model_state,
            entry_slots if entry_slots is not None else slots,
        )
        box = {"state": carried, "model_state": model_state, "slots": slots,
               "err": box_err if use_err else None}
        if self._elastic is not None:
            # every checkpoint from this fit (periodic trigger, preemption,
            # and the elastic coordination point) routes onto the per-host-
            # sharded fleet format, sliced straight off the live flat master
            self._fleet_writer = self._make_fleet_writer(fp, box, mesh)
        batch_sh = NamedSharding(mesh, P(axis))
        if jax.process_count() == 1:
            # commit straight to the step's input sharding in ONE host→device
            # hop — a batch already committed to P(axis) dispatches into the
            # SPMD program with zero resharding in front of it
            def commit(tree):
                return _tm(lambda a: jax.device_put(a, batch_sh), tree)
        else:
            commit = self._make_batch_placer(mesh, axis)  # per-host shards

        if self.async_placement:
            # sharding commit runs in the PREFETCH worker: the transfer
            # overlaps the in-flight step's compute (span data proves the
            # overlap — the place_batch span nests under prefetch/, and the
            # driver's dispatch seam shrinks to the bare enqueue)
            def place_pair(x, t):
                with obs_span("place_batch"):
                    return commit(x), commit(t)

            self._place_batch = place_pair
        else:
            self._place_batch = None  # serialized baseline (see __init__)

        def run_iteration(batch, lr: float):
            # the same three spans as LocalOptimizer's run_iteration, under
            # the drive loop's `dispatch`
            with obs_span("step_args"):
                if self.async_placement:
                    x, t = batch.get_input(), batch.get_target()  # already placed
                else:
                    with obs_span("place_batch"):  # on the DRIVER thread: this
                        x = commit(batch.get_input())  # transfer serializes in
                        t = commit(batch.get_target())  # front of the dispatch
                args = (box["state"], box["model_state"], box["slots"])
                if use_err:
                    args = args + (box["err"],)
                args = args + (
                    x,
                    t,
                    jnp.asarray(lr, jnp.float32),
                    jnp.asarray(state["neval"]),
                    RandomGenerator.next_key(),
                )
                self._capture_step_specs(step_fn, args)
            with obs_span("step_call"):
                outs = step_fn(*args)
            with obs_span("model_sync"):
                del args  # the inputs' array objects are freed inside the span
                if use_err:
                    (box["state"], box["model_state"], box["slots"],
                     box["err"], loss) = outs[:5]
                    tail = 5
                else:
                    (box["state"], box["model_state"], box["slots"],
                     loss) = outs[:4]
                    tail = 4
                if not flat_mode:
                    # flat mode deliberately skips the per-step model sync: the
                    # tree materialization is exactly the params-sized copy the
                    # flat layout kills (cold seams go through get_params below)
                    model.set_parameters(box["state"])
                model.set_state(box["model_state"])
            if hm is not None:  # health stats ride the same one-step-late pull
                return loss, outs[tail]
            return loss  # device array — _drive_loop pulls it one step later

        if flat_mode:
            get_params, get_slots = self._flat_state_thunks(
                fp, box, "state", "slots"
            )
        else:
            get_params = lambda: box["state"]  # noqa: E731
            get_slots = lambda: box["slots"]  # noqa: E731
        self._drive_loop(
            run_iteration,
            get_params,
            get_slots,
            lambda: box["model_state"],
        )
        model.set_parameters(get_params())
        model.set_state(box["model_state"])
        return model
