"""First-class pipeline & expert parallelism (PR 17): PipelineOptimizer /
ExpertParallelOptimizer production-path locks.

The MULTICHIP dryruns proved ``pipeline_apply``/``moe_ffn`` compile and
step; these tests lock the promoted optimizer paths to the guarantees the
other production optimizers carry, on the virtual 8-device CPU platform
(conftest):

* **parity** — pp, dp×pp, ep and dp×ep training match the LocalOptimizer
  oracle parameter-for-parameter on ragged multi-epoch fits (the stacked
  layouts change WHERE math runs, never WHAT it computes; dp×ep uses
  ``capacity_factor`` headroom so per-group capacity accounting cannot
  diverge from the dense oracle — docs/parallelism.md);
* **hot-path invariants** — EXACTLY one compile across the ragged fit
  (pad+mask through the ``unreduced`` seam), donation on, retry reuses the
  cached step;
* **program locks** — the lowered step carries the schedule's collectives
  (``collective_permute`` ring hops / ``all_to_all`` dispatch) and NO
  stage-stack all-gather (the optimizer update runs sharded in place);
* **observability** — perf records stamp ``pipe_bubble_frac`` (the GPipe
  idle fraction (S-1)/(n_micro+S-1)) and the per-step
  ``ppermute_bytes``/``all_to_all_bytes`` wire cost, and
  ``tools/obs_report.py`` validates and renders them;
* **resilience** — injected faults at the ``dispatch`` seam recover, and
  checkpoint/resume round-trips bit-identically (slots persist in the
  single-path tree layout).
"""

import importlib.util
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.dataset import AbstractDataSet
from bigdl_tpu.obs import Telemetry
from bigdl_tpu.obs.perf import PerfConfig, pipeline_bubble_fraction
from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger
from bigdl_tpu.parallel import (
    ExpertParallelOptimizer,
    ParallelCompositionError,
    PipelineOptimizer,
    make_mesh,
)
from bigdl_tpu.utils.random import RandomGenerator

# the report tool is the schema gate for telemetry records (tools/ is not a
# package — same loading idiom as tests/test_obs.py)
_spec = importlib.util.spec_from_file_location(
    "obs_report",
    Path(__file__).resolve().parent.parent / "tools" / "obs_report.py",
)
obs_report = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = obs_report
_spec.loader.exec_module(obs_report)

N_STAGES = 4  # = n_experts; fits both the 4-device and 2x4 meshes


def _problem(n=56, d=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.integers(0, classes, n)
    return x, y


def _pipe_model(d=8, classes=4):
    return nn.Sequential(
        nn.Linear(d, 16),
        nn.PipelinedBlocks(
            nn.Sequential(nn.Linear(16, 16), nn.Tanh()), N_STAGES
        ),
        nn.Linear(16, classes),
        nn.LogSoftMax(),
    )


def _moe_model(d=8, classes=4):
    # capacity_factor=4.0: with dp x ep the capacity budget is per (data
    # row, source shard) — headroom keeps routing lossless on every mesh so
    # the dense oracle stays an exact reference (docs/parallelism.md)
    return nn.Sequential(
        nn.Linear(d, 16),
        nn.MoE(N_STAGES, ffn_size=16, capacity_factor=4.0),
        nn.Linear(16, classes),
        nn.LogSoftMax(),
    )


def _leaves(params):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(params)]


def _fit(opt, epochs=2, perf=False, seed=11):
    """2-epoch ragged fit (56 rows / batch 16 -> the last batch is short)
    with telemetry; results pulled to host before returning — interleaving
    meshes over different device subsets in one process needs the
    block_until_ready barrier (parallel/__init__ virtual-CPU-mesh caveat)."""
    RandomGenerator.set_seed(seed)
    tel = Telemetry()
    opt.set_optim_method(SGD(learningrate=0.1))
    opt.set_end_when(Trigger.max_epoch(epochs))
    opt.set_telemetry(tel)
    if perf:
        opt.set_perf(
            PerfConfig(every_n_steps=2, baseline_steps=2, window=2,
                       capture=False)
        )
    opt.optimize()
    jax.block_until_ready(jax.tree_util.tree_leaves(
        opt.model.get_parameters()))
    return opt, tel


class _FailingDataSet(AbstractDataSet):
    """Raises once at a chosen global batch index, then behaves normally
    (the tests/test_failure_retry.py transient-fault idiom)."""

    def __init__(self, base, fail_at: int):
        self.base = base
        self.fail_at = fail_at
        self.served = 0
        self.failed = False

    def size(self):
        return self.base.size()

    def shuffle(self, epoch=None):
        self.base.shuffle(epoch)

    def data(self, train):
        for b in self.base.data(train):
            if train and not self.failed and self.served == self.fail_at:
                self.failed = True
                raise RuntimeError("injected executor failure")
            if train:
                self.served += 1
            yield b


# --------------------------------------------------------------------------
# shared fits (module scope: the compile-heavy fixtures amortize across the
# parity / program-lock / observability assertions below)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pp_oracle():
    x, y = _problem()
    opt, _ = _fit(LocalOptimizer(
        _pipe_model(), DataSet.array(x, y, batch_size=16),
        nn.ClassNLLCriterion()))
    return _leaves(opt.model.get_parameters())


@pytest.fixture(scope="module")
def pp_fit():
    x, y = _problem()
    mesh = make_mesh({"pipe": N_STAGES}, devices=jax.devices()[:N_STAGES])
    return _fit(PipelineOptimizer(
        _pipe_model(), DataSet.array(x, y, batch_size=16),
        nn.ClassNLLCriterion(), mesh=mesh), perf=True)


@pytest.fixture(scope="module")
def ep_oracle():
    x, y = _problem()
    opt, _ = _fit(LocalOptimizer(
        _moe_model(), DataSet.array(x, y, batch_size=16),
        nn.ClassNLLCriterion()))
    return _leaves(opt.model.get_parameters())


@pytest.fixture(scope="module")
def ep_fit():
    x, y = _problem()
    mesh = make_mesh({"expert": N_STAGES}, devices=jax.devices()[:N_STAGES])
    return _fit(ExpertParallelOptimizer(
        _moe_model(), DataSet.array(x, y, batch_size=16),
        nn.ClassNLLCriterion(), mesh=mesh), perf=True)


def _hlo(opt) -> str:
    fn, specs = opt._step_export_info
    return fn.lower(*specs).as_text()


# --------------------------------------------------------------------------
# parity: the promoted paths train identically to the local oracle
# --------------------------------------------------------------------------

class TestPipelineParity:
    def test_params_match_oracle(self, pp_fit, pp_oracle):
        opt, _ = pp_fit
        for a, b in zip(_leaves(opt.model.get_parameters()), pp_oracle):
            np.testing.assert_allclose(a, b, atol=1e-6)

    def test_exactly_one_compile_on_ragged_fit(self, pp_fit):
        opt, tel = pp_fit
        assert opt._jit_step._cache_size() == 1
        assert tel.compile_count == 1

    def test_hlo_carries_ppermute_no_stage_allgather(self, pp_fit):
        from bigdl_tpu.obs.profiler import collective_bytes

        opt, _ = pp_fit
        hlo = _hlo(opt)
        assert "collective_permute" in hlo or "collective-permute" in hlo
        # the stage stack must never be re-materialized: the optimizer
        # update runs sharded over P('pipe'), so any all-gather in the
        # program is smaller than one stacked stage-param tree
        stack_bytes = sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for path, a in jax.tree_util.tree_leaves_with_path(
                opt.model.get_parameters())
            if "stages" in jax.tree_util.keystr(path)
        )
        assert stack_bytes > 0
        ag = collective_bytes(hlo)["all_gather_bytes"]
        assert ag < stack_bytes, (ag, stack_bytes)

    def test_bubble_frac_stamped_from_schedule(self, pp_fit):
        opt, _ = pp_fit
        # the closed form (S-1)/(n_micro+S-1); default n_micro = S
        want = (N_STAGES - 1) / (N_STAGES + N_STAGES - 1)
        assert opt._perf.pipe_bubble_frac == round(want, 6)
        assert opt._perf.pipe_bubble_frac == round(
            pipeline_bubble_fraction(N_STAGES, N_STAGES), 6)

    def test_n_micro_override_changes_bubble(self):
        x, y = _problem(n=64)
        mesh = make_mesh({"pipe": N_STAGES},
                         devices=jax.devices()[:N_STAGES])
        opt = PipelineOptimizer(
            _pipe_model(), DataSet.array(x, y, batch_size=16),
            nn.ClassNLLCriterion(), mesh=mesh, n_micro=8)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_iteration(1))
        opt.optimize()
        jax.block_until_ready(jax.tree_util.tree_leaves(
            opt.model.get_parameters()))
        assert opt._perf.pipe_bubble_frac == round(
            pipeline_bubble_fraction(N_STAGES, 8), 6)

    def test_perf_records_carry_schedule_and_wire_cost(self, pp_fit):
        _, tel = pp_fit
        perfs = [r for r in tel.ring.records if r["type"] == "perf"]
        assert perfs
        last = perfs[-1]
        assert last["pipe_bubble_frac"] == round(
            pipeline_bubble_fraction(N_STAGES, N_STAGES), 6)
        assert last["ppermute_bytes"] > 0
        for r in perfs:
            obs_report.validate_record(r)
        text = obs_report.render(obs_report.summarize(list(tel.ring.records)))
        line = [l for l in text.splitlines() if "parallelism" in l]
        assert line and "pipe-bubble" in line[0] and "ppermute" in line[0]


class TestExpertParity:
    def test_params_match_oracle(self, ep_fit, ep_oracle):
        opt, _ = ep_fit
        for a, b in zip(_leaves(opt.model.get_parameters()), ep_oracle):
            np.testing.assert_allclose(a, b, atol=1e-6)

    def test_exactly_one_compile_on_ragged_fit(self, ep_fit):
        opt, tel = ep_fit
        assert opt._jit_step._cache_size() == 1
        assert tel.compile_count == 1

    def test_hlo_carries_all_to_all(self, ep_fit):
        opt, _ = ep_fit
        hlo = _hlo(opt)
        assert "all_to_all" in hlo or "all-to-all" in hlo

    def test_perf_records_carry_wire_cost(self, ep_fit):
        _, tel = ep_fit
        perfs = [r for r in tel.ring.records if r["type"] == "perf"]
        assert perfs
        last = perfs[-1]
        assert last["all_to_all_bytes"] > 0
        assert "pipe_bubble_frac" not in last  # ep has no GPipe schedule
        for r in perfs:
            obs_report.validate_record(r)
        text = obs_report.render(obs_report.summarize(list(tel.ring.records)))
        line = [l for l in text.splitlines() if "parallelism" in l]
        assert line and "all_to_all" in line[0]


class TestComposition:
    """dp x pp and dp x ep: the batch shards over a second mesh axis and the
    trajectory still matches the single-device oracle."""

    def test_dp_pp_matches_oracle(self, pp_oracle):
        x, y = _problem()
        mesh = make_mesh({"data": 2, "pipe": N_STAGES})
        opt, tel = _fit(PipelineOptimizer(
            _pipe_model(), DataSet.array(x, y, batch_size=16),
            nn.ClassNLLCriterion(), mesh=mesh, data_axis="data"))
        for a, b in zip(_leaves(opt.model.get_parameters()), pp_oracle):
            np.testing.assert_allclose(a, b, atol=1e-6)
        assert opt._jit_step._cache_size() == 1
        assert tel.compile_count == 1

    def test_dp_ep_matches_oracle(self, ep_oracle):
        x, y = _problem()
        mesh = make_mesh({"data": 2, "expert": N_STAGES})
        opt, tel = _fit(ExpertParallelOptimizer(
            _moe_model(), DataSet.array(x, y, batch_size=16),
            nn.ClassNLLCriterion(), mesh=mesh, data_axis="data"))
        for a, b in zip(_leaves(opt.model.get_parameters()), ep_oracle):
            np.testing.assert_allclose(a, b, atol=1e-6)
        assert opt._jit_step._cache_size() == 1
        assert tel.compile_count == 1


# --------------------------------------------------------------------------
# construction contracts: typed refusals, mesh/batch validation
# --------------------------------------------------------------------------

class TestRefusals:
    @pytest.mark.parametrize("cls,model_fn", [
        (PipelineOptimizer, _pipe_model),
        (ExpertParallelOptimizer, _moe_model),
    ])
    def test_incompatible_composition_is_typed(self, cls, model_fn):
        x, y = _problem(n=16)
        with pytest.raises(ParallelCompositionError) as ei:
            cls(model_fn(), DataSet.array(x, y, batch_size=16),
                nn.ClassNLLCriterion(), comms_dtype="bfloat16")
        # subclass of ValueError: pre-PR callers catching ValueError keep
        # working; the message names the incompatible layout
        assert isinstance(ei.value, ValueError)
        assert "incompatible" in str(ei.value)

    def test_set_micro_batches_refused(self):
        x, y = _problem(n=16)
        opt = PipelineOptimizer(
            _pipe_model(), DataSet.array(x, y, batch_size=16),
            nn.ClassNLLCriterion())
        with pytest.raises(NotImplementedError, match="n_micro"):
            opt.set_micro_batches(2)

    def test_mesh_missing_axis_fails_loudly(self):
        x, y = _problem(n=16)
        mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
        opt = PipelineOptimizer(
            _pipe_model(), DataSet.array(x, y, batch_size=16),
            nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_iteration(1))
        with pytest.raises(ValueError, match="make_mesh"):
            opt.optimize()

    def test_batch_must_fill_schedule_grid(self):
        x, y = _problem(n=12)
        mesh = make_mesh({"pipe": N_STAGES},
                         devices=jax.devices()[:N_STAGES])
        opt = PipelineOptimizer(
            _pipe_model(), DataSet.array(x, y, batch_size=6),
            nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_iteration(1))
        with pytest.raises(ValueError, match="n_micro"):
            opt.optimize()

    def test_model_without_parallel_module_fails_loudly(self):
        x, y = _problem(n=16)
        mesh = make_mesh({"pipe": N_STAGES},
                         devices=jax.devices()[:N_STAGES])
        plain = nn.Sequential(nn.Linear(8, 4), nn.LogSoftMax())
        opt = PipelineOptimizer(
            plain, DataSet.array(x, y, batch_size=16),
            nn.ClassNLLCriterion(), mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_iteration(1))
        with pytest.raises(ValueError, match="PipelinedBlocks"):
            opt.optimize()


# --------------------------------------------------------------------------
# resilience: retry / chaos / checkpoint-resume on the pipeline path
# --------------------------------------------------------------------------

class TestResilience:
    def _pp_opt(self, ds, tmp_path=None):
        mesh = make_mesh({"pipe": N_STAGES},
                         devices=jax.devices()[:N_STAGES])
        opt = PipelineOptimizer(_pipe_model(), ds, nn.ClassNLLCriterion(),
                                mesh=mesh)
        opt.set_optim_method(SGD(learningrate=0.1))
        if tmp_path is not None:
            opt.set_checkpoint(str(tmp_path), Trigger.several_iteration(2))
        return opt

    def test_retry_reuses_cached_step(self, tmp_path):
        RandomGenerator.set_seed(13)
        x, y = _problem(n=64)
        ds = _FailingDataSet(DataSet.array(x, y, batch_size=16), fail_at=5)
        tel = Telemetry()
        opt = self._pp_opt(ds, tmp_path)
        opt.set_end_when(Trigger.max_iteration(8))
        opt.set_retry_times(2)
        opt.set_telemetry(tel)
        opt.optimize()
        jax.block_until_ready(jax.tree_util.tree_leaves(
            opt.model.get_parameters()))
        assert ds.failed
        assert any(r["type"] == "retry" for r in tel.ring.records)
        # the resumed attempt hits the SAME compiled program
        assert opt._jit_step._cache_size() == 1
        assert tel.compile_count == 1
        assert opt.optim_method.state["neval"] >= 8

    def test_chaos_dispatch_seam_recovers(self, tmp_path):
        from bigdl_tpu.resilience import FailurePolicy, FaultPlan

        RandomGenerator.set_seed(13)
        x, y = _problem(n=64)
        tel = Telemetry()
        plan = FaultPlan(telemetry=tel).arm("dispatch", at_hit=4)
        opt = self._pp_opt(DataSet.array(x, y, batch_size=16), tmp_path)
        opt.set_end_when(Trigger.max_iteration(8))
        opt.set_failure_policy(FailurePolicy(backoff_base_s=0.0))
        opt.set_telemetry(tel)
        with plan:
            opt.optimize()
        jax.block_until_ready(jax.tree_util.tree_leaves(
            opt.model.get_parameters()))
        assert plan.events and any(
            e["seam"] == "dispatch" for e in plan.events)
        types = {r["type"] for r in tel.ring.records}
        assert "retry" in types and "fault_injected" in types
        assert opt.optim_method.state["neval"] >= 8
        for leaf in _leaves(opt.model.get_parameters()):
            assert np.all(np.isfinite(leaf))

    def test_checkpoint_resume_roundtrip(self, tmp_path):
        from bigdl_tpu.utils import serialization as ser

        x, y = _problem(n=64)
        # gold: the uninterrupted 2-epoch run
        RandomGenerator.set_seed(24)
        gold = self._pp_opt(DataSet.array(x, y, batch_size=16))
        gold.set_end_when(Trigger.max_iteration(8))
        gold.optimize()
        ref = _leaves(gold.model.get_parameters())
        jax.block_until_ready(jax.tree_util.tree_leaves(ref))

        ckpt = tmp_path / "ckpt"
        RandomGenerator.set_seed(24)
        opt1 = self._pp_opt(DataSet.array(x, y, batch_size=16), ckpt)
        opt1.set_end_when(Trigger.max_iteration(4))
        opt1.optimize()
        step = ser.latest_checkpoint_step(str(ckpt))
        assert step is not None
        # bit-compatibility with the single-path layout: slots land in tree
        # view, so any optimizer can resume this checkpoint
        assert ser.checkpoint_manifest(str(ckpt), step)["slot_layout"] == \
            "tree"

        RandomGenerator.set_seed(24)
        opt2 = self._pp_opt(DataSet.array(x, y, batch_size=16))
        opt2.set_end_when(Trigger.max_iteration(8))
        opt2.resume(str(ckpt))
        opt2.optimize()
        got = _leaves(opt2.model.get_parameters())
        jax.block_until_ready(jax.tree_util.tree_leaves(got))
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(a, b)
