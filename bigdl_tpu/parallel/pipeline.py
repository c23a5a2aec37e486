"""Pipeline parallelism — GPipe microbatch schedule over a ``pipe`` mesh axis.

Beyond-reference capability (the reference scales only by data parallelism
over Spark executors; SURVEY.md §2.5 parallelism-inventory row): models too
deep for one chip's HBM split into S stages laid out along a mesh axis, and
microbatches stream through the stages with ``lax.ppermute`` hops riding the
ICI ring — the TPU-native form of GPipe (Huang et al. 2019, PAPERS.md).

Design, the jax/SPMD way:

* one ``shard_map`` program; every device runs the SAME trace. Stage
  identity is ``lax.axis_index('pipe')``; stage parameters are a STACKED
  pytree (leading dim S) sharded on 'pipe', so each device holds exactly
  its own stage's weights — the classic identical-stage formulation (a
  transformer's block stack). Head/tail layers stay outside (replicated).
* the schedule is a ``lax.scan`` over T = n_micro + S - 1 ticks. At tick t
  stage s computes microbatch ``t - s`` (validity-masked), then the
  activation ring-shifts one hop (+1) via ``ppermute``. No data-dependent
  control flow — XLA sees a static loop.
* backward is NOT hand-written: ``ppermute`` is differentiable (its
  transpose is the reverse shift), so ``jax.grad`` through the scan yields
  the reverse pipeline schedule automatically — the same property the
  framework leans on everywhere else (SURVEY §3.3: derive, don't port).
* the last stage's outputs are broadcast back with a masked ``psum``, so
  the caller sees a replicated (B, ...) result and can compose the loss
  data-parallel-style.

Interpret/CPU-mesh friendly: tested on the virtual 8-device mesh like the
other parallel paths (tests/test_pipeline.py). Production entry point:
:class:`~bigdl_tpu.parallel.pipeline_optimizer.PipelineOptimizer` drives
this schedule through ``nn.PipelinedBlocks`` with the full optimizer
guarantee set (donation, 1-compile ragged fits, health/perf/resilience,
checkpoints); ``__graft_entry__.dryrun_multichip`` phase 6 smoke-tests the
same path on 8 devices.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

_tm = jax.tree_util.tree_map


def _local_stage(stacked_shard):
    """Local (1, ...) shard of the stacked stage params -> this stage's (...).

    Inside shard_map each device's shard of the P('pipe')-sharded stack has
    leading dim exactly 1 (enforced by the caller's stage-count check)."""
    return _tm(lambda a: a[0], stacked_shard)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params,
    x: jax.Array,
    mesh: Mesh,
    axis: str = "pipe",
    n_micro: Optional[int] = None,
    batch_axis: Optional[str] = None,
    remat_stages: bool = False,
):
    """Run ``x`` through S pipeline stages of ``stage_fn`` (GPipe schedule).

    Args:
        stage_fn: ``(params_one_stage, h) -> h`` — one stage's computation.
            Activations must keep a constant shape across stages (the
            identical-stage formulation; put reshaping head/tail layers
            outside the pipeline; see ``pipeline_apply_hetero`` for
            per-stage heterogeneity).
        stage_params: pytree whose leaves have leading dim S (stage-stacked).
        x: (B, ...) global batch.
        mesh: mesh carrying ``axis`` of size S (and ``batch_axis`` if given).
        n_micro: microbatch count (divides the per-dp-shard batch; default S
            — the GPipe bubble fraction is (S-1)/(n_micro+S-1), so more
            microbatches amortize it).
        batch_axis: optional second mesh axis for dp×pp composition: the
            batch dim is sharded over it (each dp shard runs its own
            pipeline over the same stage weights) instead of replicated.
        remat_stages: checkpoint each stage invocation
            (``jax.checkpoint``): the backward recomputes INTRA-stage
            activations instead of storing them per tick, so stashed
            memory per device drops from every stage-internal
            intermediate x (n_micro + S - 1) ticks to just the tick
            boundaries — most of 1F1B's activation-memory benefit while
            keeping the static GPipe schedule (outputs and gradients are
            bit-identical, only the autodiff schedule changes). For
            ``pipeline_apply_hetero`` pass pre-checkpointed
            ``stage_fns`` instead.

    Returns (B, ...) outputs (replicated over ``axis``; sharded over
    ``batch_axis`` when given) — differentiable end to end.
    """
    if remat_stages:
        # prevent_cse=False: only ever called inside the tick scan (safe
        # per jax.checkpoint docs; avoids optimization barriers)
        stage_fn = jax.checkpoint(stage_fn, prevent_cse=False)
    s_stages = mesh.shape[axis]
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] != s_stages:
            raise ValueError(
                f"stage_params leading dim {leaf.shape[0]} != pipeline "
                f"stages {s_stages} — a mismatched stack would silently "
                "run only a subset of stages")
    if n_micro is None:
        n_micro = s_stages
    b_local = x.shape[0]
    if batch_axis is not None:
        if batch_axis == axis:
            raise ValueError(
                f"batch_axis must differ from the pipeline axis {axis!r}: "
                "sharding the batch over the stage axis would feed each "
                "stage only its own shard (silently wrong output)")
        if batch_axis not in mesh.shape:
            raise ValueError(
                f"batch_axis {batch_axis!r} not in mesh axes "
                f"{tuple(mesh.shape)}")
        dp = mesh.shape[batch_axis]
        if x.shape[0] % dp:
            raise ValueError(
                f"batch {x.shape[0]} not divisible by {batch_axis!r} mesh "
                f"axis size {dp}")
        b_local = x.shape[0] // dp
    if b_local % n_micro:
        raise ValueError(
            f"per-shard batch {b_local} not divisible by n_micro {n_micro}")

    def per_device(params_local, x_all):
        stage = lax.axis_index(axis)
        p = _local_stage(params_local)
        b = x_all.shape[0]  # local dp-shard batch
        micro = x_all.reshape(n_micro, b // n_micro, *x_all.shape[1:])
        t_total = n_micro + s_stages - 1
        zero_h = jnp.zeros_like(micro[0])
        out_buf = jnp.zeros((n_micro,) + zero_h.shape, zero_h.dtype)

        def tick(carry, t):
            recv, out_buf = carry
            mb = t - stage  # which microbatch this stage works on now
            valid = (mb >= 0) & (mb < n_micro)
            # stage 0 reads from the batch; later stages from the ring
            feed = lax.dynamic_index_in_dim(
                micro, jnp.clip(mb, 0, n_micro - 1), keepdims=False)
            h_in = jnp.where(stage == 0, feed, recv)
            # bubble ticks run stage_fn too (static schedule) — feed ONES,
            # not the real data or zeros: masking only the OUTPUT leaves
            # the where-NaN autodiff trap armed for stage_fns that are
            # non-finite at zero (unguarded norms etc.)
            h_in = jnp.where(valid, h_in, jnp.ones_like(h_in))
            h_out = stage_fn(p, h_in)
            h_out = jnp.where(valid, h_out, zero_h)
            # last stage banks its finished microbatch
            is_last = stage == s_stages - 1
            out_buf = lax.dynamic_update_index_in_dim(
                out_buf,
                jnp.where(valid & is_last, h_out, lax.dynamic_index_in_dim(
                    out_buf, jnp.clip(mb, 0, n_micro - 1), keepdims=False)),
                jnp.clip(mb, 0, n_micro - 1), 0)
            # ring-shift activations one stage forward
            sent = lax.ppermute(
                h_out, axis,
                [(i, (i + 1) % s_stages) for i in range(s_stages)])
            return (sent, out_buf), None

        (_, out_buf), _ = lax.scan(
            tick, (zero_h, out_buf), jnp.arange(t_total))
        # broadcast the last stage's outputs to every device
        mine = jnp.where(stage == s_stages - 1, out_buf,
                         jnp.zeros_like(out_buf))
        full = lax.psum(mine, axis)
        return full.reshape(b, *x_all.shape[1:])

    x_spec = P(batch_axis) if batch_axis is not None else P()
    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(axis), x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stage_params, x)


def stack_stage_params(per_stage_params):
    """List of S identical-structure pytrees -> one stage-stacked pytree."""
    return _tm(lambda *leaves: jnp.stack(leaves), *per_stage_params)


# --------------------------------------------------------------------- hetero


def pipeline_apply_hetero(
    stage_fns,
    per_stage_params,
    x: jax.Array,
    mesh: Mesh,
    axis: str = "pipe",
    n_micro: Optional[int] = None,
    skip_bubble_compute: bool = True,
):
    """GPipe schedule over HETEROGENEOUS stages (VERDICT r4 next #6).

    Unlike ``pipeline_apply``, each stage may have its own parameter tree
    AND its own activation shape (e.g. a CNN whose stages downsample):

    * per-stage params are flattened to one vector each, zero-padded to the
      longest and stacked (S, Lp) — shardable on the ``pipe`` axis even
      though the trees differ (every device still holds only its own
      stage's weights, plus bounded padding).
    * activations ride the ``ppermute`` ring as a flat carrier vector
      sized to the LARGEST inter-stage activation; a stage-indexed
      ``lax.switch`` unflattens the carrier to that stage's static shapes,
      runs its ``stage_fn``, and re-flattens. The switch is the
      TPU-compatible form of per-device heterogeneity: every device traces
      all S branches once, executes only its own.
    * ``skip_bubble_compute=True`` wraps the stage body in ``lax.cond`` so
      bubble ticks (the (S-1)/(n_micro+S-1) schedule fraction) skip the
      stage computation entirely instead of burning it on dummy data —
      and, as a bonus, the where-NaN autodiff trap of dummy inputs never
      arms.

    Args:
        stage_fns: S callables ``(params_i, h) -> h_next`` (may change
            shape; must preserve the microbatch leading dim).
        per_stage_params: S pytrees (structures may differ).
        x: (B, ...) replicated global batch.
        mesh / axis / n_micro: as in ``pipeline_apply``.

    Returns the final stage's outputs (B, ...), replicated.
    """
    s_stages = mesh.shape[axis]
    if len(stage_fns) != s_stages or len(per_stage_params) != s_stages:
        raise ValueError(
            f"got {len(stage_fns)} stage_fns / {len(per_stage_params)} "
            f"param trees for a {s_stages}-stage {axis!r} mesh axis")
    if n_micro is None:
        n_micro = s_stages
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by n_micro {n_micro}")
    mb = b // n_micro
    mb_shape = (mb,) + tuple(x.shape[1:])

    # chain the per-stage activation specs (static shapes, traced once)
    specs = [jax.ShapeDtypeStruct(mb_shape, x.dtype)]
    for fn, p in zip(stage_fns, per_stage_params):
        out_spec = jax.eval_shape(fn, p, specs[-1])
        if not isinstance(out_spec, jax.ShapeDtypeStruct):
            raise ValueError("stage_fns must map array -> array")
        if out_spec.shape[0] != mb:
            raise ValueError(
                f"stage output leading dim {out_spec.shape[0]} != "
                f"microbatch {mb} — stages must preserve the batch dim")
        specs.append(out_spec)
    act_dtypes = {s.dtype for s in specs}
    if len(act_dtypes) != 1:
        raise ValueError(f"activations must share one dtype, got {act_dtypes}")
    act_dtype = specs[0].dtype
    sizes = [int(np.prod(s.shape)) for s in specs]
    l_h = max(sizes)

    # ravel_pytree: leaf dtypes are restored exactly by each stage's
    # unravel closure, so mixed-dtype trees are fine as long as the
    # PROMOTED flat dtypes agree across stages (they must stack)
    from jax.flatten_util import ravel_pytree

    flats, unravels = [], []
    for p in per_stage_params:
        f, unravel = ravel_pytree(p)
        flats.append(f)
        unravels.append(unravel)
    p_dtypes = {f.dtype for f in flats}
    if len(p_dtypes) != 1:
        raise ValueError(
            f"stacked stage params must share one flat dtype, got {p_dtypes}")
    l_p = max(int(f.shape[0]) for f in flats)
    stacked = jnp.stack([jnp.pad(f, (0, l_p - f.shape[0])) for f in flats])
    flat_sizes = [int(f.shape[0]) for f in flats]
    out_size = sizes[-1]
    out_shape = specs[-1].shape

    def per_device(params_local, x_all):
        stage = lax.axis_index(axis)
        flat_p = params_local[0]
        micro = x_all.reshape(n_micro, *mb_shape)
        t_total = n_micro + s_stages - 1

        def make_branch(i):
            def branch(fp, fh):
                p = unravels[i](fp[:flat_sizes[i]])
                h = fh[:sizes[i]].reshape(specs[i].shape)
                y = stage_fns[i](p, h)
                fy = jnp.ravel(y)
                return jnp.pad(fy, (0, l_h - sizes[i + 1]))
            return branch

        branches = [make_branch(i) for i in range(s_stages)]
        zero_carrier = jnp.zeros((l_h,), act_dtype)

        def run_stage(fp, fh):
            return lax.switch(stage, branches, fp, fh)

        def tick(carry, t):
            recv, out_buf = carry
            mb_idx = t - stage
            valid = (mb_idx >= 0) & (mb_idx < n_micro)
            feed = jnp.ravel(lax.dynamic_index_in_dim(
                micro, jnp.clip(mb_idx, 0, n_micro - 1), keepdims=False))
            feed = jnp.pad(feed, (0, l_h - feed.shape[0]))
            h_in = jnp.where(stage == 0, feed, recv)
            if skip_bubble_compute:
                h_out = lax.cond(valid, lambda: run_stage(flat_p, h_in),
                                 lambda: zero_carrier)
            else:
                h_in = jnp.where(valid, h_in, jnp.ones_like(h_in))
                h_out = jnp.where(valid, run_stage(flat_p, h_in),
                                  zero_carrier)
            is_last = stage == s_stages - 1
            prev = lax.dynamic_index_in_dim(
                out_buf, jnp.clip(mb_idx, 0, n_micro - 1), keepdims=False)
            out_buf = lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(valid & is_last, h_out[:out_size], prev),
                jnp.clip(mb_idx, 0, n_micro - 1), 0)
            sent = lax.ppermute(
                h_out, axis,
                [(i, (i + 1) % s_stages) for i in range(s_stages)])
            return (sent, out_buf), None

        out_buf0 = jnp.zeros((n_micro, out_size), act_dtype)
        (_, out_buf), _ = lax.scan(
            tick, (zero_carrier, out_buf0), jnp.arange(t_total))
        mine = jnp.where(stage == s_stages - 1, out_buf,
                         jnp.zeros_like(out_buf))
        full = lax.psum(mine, axis)
        return full.reshape(n_micro * mb, *out_shape[1:])

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stacked, x)
