"""Unified telemetry layer (bigdl_tpu.obs): event-stream schema, exporter
fan-out agreement, stall watchdog (fake clock — zero sleeps), CPU
memory-stats fallback, run-dir convention, and the donation-regression
canary: a 2-epoch ragged fit on every execution path must report EXACTLY one
compile through telemetry (PR 2's recompile elimination as an observable
invariant)."""

import importlib.util
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.dataset import LocalArrayDataSet, SampleToMiniBatch
from bigdl_tpu.obs import (
    JsonlExporter,
    Metrics,
    RingBufferExporter,
    StallWatchdog,
    SummaryExporter,
    Telemetry,
    device_memory_stats,
)
from bigdl_tpu.optim import LocalOptimizer, Predictor, SGD, Trigger
from bigdl_tpu.utils.random import RandomGenerator
from bigdl_tpu.visualization import TrainSummary

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module", autouse=True)
def _engine_isolation():
    """The Distri canary freezes an 8-device Engine topology; reset around
    the module so it neither inherits nor leaks it (later files build
    single-device Predictors whose batch sizes are not divisible by 8)."""
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    yield
    Engine.reset()

# the report tool is the schema gate: load it once so live Telemetry output
# is validated against the SAME table the CI selftest uses
spec = importlib.util.spec_from_file_location(
    "obs_report", REPO / "tools" / "obs_report.py"
)
obs_report = importlib.util.module_from_spec(spec)
sys.modules[spec.name] = obs_report
spec.loader.exec_module(obs_report)


def _problem(n=20, d=5, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.integers(0, classes, n)
    return x, y


def _model(d=5, classes=3):
    return nn.Sequential(
        nn.Linear(d, 16), nn.Tanh(), nn.Linear(16, classes), nn.LogSoftMax()
    )


def _ragged_ds(x, y, batch=8):
    """[8, 8, 4] epochs: the 4-row tail exercises the pad/mask seam."""
    return LocalArrayDataSet(
        x, y, transformer=SampleToMiniBatch(batch), batch_size=batch
    )


def _fit_local(tel, max_epoch=2):
    RandomGenerator.set_seed(7)
    x, y = _problem()
    opt = LocalOptimizer(_model(), _ragged_ds(x, y), nn.ClassNLLCriterion())
    opt.set_optim_method(SGD(learningrate=0.2, momentum=0.9))
    opt.set_end_when(Trigger.max_epoch(max_epoch))
    opt.set_telemetry(tel)
    opt.optimize()
    return opt


# --------------------------------------------------------------------------
class TestMetrics:
    def test_time_records_despite_exception(self):
        """Satellite fix: the timed block raising must NOT drop the sample —
        the retry path's failing steps were silently missing from averages."""
        m = Metrics()
        with pytest.raises(RuntimeError):
            with m.time("step"):
                raise RuntimeError("boom")
        assert m._counts.get("step") == 1
        assert m.average("step") >= 0.0

    def test_alias_import_path(self):
        from bigdl_tpu.optim.metrics import Metrics as Old

        assert Old is Metrics


# --------------------------------------------------------------------------
class TestStallWatchdog:
    def _fake(self):
        clock = {"t": 0.0}
        return clock, (lambda: clock["t"])

    def test_stall_detection_and_rearm(self):
        clock, fn = self._fake()
        hits = []
        wd = StallWatchdog(k=2.0, min_timeout_s=1.0, clock=fn,
                           on_stall=hits.append)
        wd.notify_step(0.5)  # median 0.5 -> deadline max(2*0.5, 1.0) = 1.0
        clock["t"] = 0.9
        assert wd.check() is None
        clock["t"] = 2.1  # waited 2.1 > 1.0: stall
        info = wd.check()
        assert info is not None and info["waited_s"] == pytest.approx(2.1)
        assert info["deadline_s"] == pytest.approx(1.0)
        assert hits == [info]
        assert wd.check() is None  # flagged once, not every poll
        clock["t"] = 3.0
        wd.notify_step(0.5)  # a completing step re-arms
        clock["t"] = 3.5
        assert wd.check() is None
        clock["t"] = 5.0
        assert wd.check() is not None
        assert wd.stall_count == 2

    def test_disarmed_until_first_step_by_default(self):
        clock, fn = self._fake()
        wd = StallWatchdog(clock=fn)
        wd._started_at = 0.0  # as start() would, without spawning the thread
        clock["t"] = 1e6  # a cold compile may legitimately take forever
        assert wd.check() is None

    def test_first_step_timeout_arms_before_any_step(self):
        clock, fn = self._fake()
        wd = StallWatchdog(first_step_timeout_s=5.0, clock=fn)
        wd._started_at = 0.0
        clock["t"] = 4.9
        assert wd.check() is None
        clock["t"] = 5.1
        assert wd.check() is not None

    def test_min_timeout_floor(self):
        clock, fn = self._fake()
        wd = StallWatchdog(k=2.0, min_timeout_s=5.0, clock=fn)
        wd.notify_step(0.001)  # sub-ms steps must not page on a GC pause
        assert wd.deadline_s() == pytest.approx(5.0)

    def test_restart_does_not_flag_idle_gap_between_runs(self):
        """A reused watchdog (one Telemetry across two fits) must reset its
        per-run state on start(): the idle gap between runs is not a stall,
        and run 2's cold compile must not be judged by run 1's median."""
        clock, fn = self._fake()
        wd = StallWatchdog(k=2.0, min_timeout_s=1.0, clock=fn)
        wd.start()
        wd.stop()
        wd.notify_step(0.5)
        clock["t"] = 1000.0  # long idle gap, then a second run starts
        wd.start()
        wd.stop()
        assert wd.check() is None  # disarmed until run 2's first step
        wd.notify_step(0.5)
        clock["t"] = 1003.0
        assert wd.check() is not None  # still armed within run 2

    def test_stall_record_reaches_telemetry_stream(self):
        clock, fn = self._fake()
        wd = StallWatchdog(k=2.0, min_timeout_s=1.0, clock=fn)
        tel = Telemetry(exporters=[RingBufferExporter()], watchdog=wd)
        wd.notify_step(0.1)
        clock["t"] = 50.0
        assert wd.check() is not None
        stalls = [r for r in tel.ring.records if r["type"] == "stall"]
        assert len(stalls) == 1
        obs_report.validate_record(stalls[0])


# --------------------------------------------------------------------------
class TestEventStream:
    def test_schema_and_compile_canary_local(self):
        tel = Telemetry()
        opt = _fit_local(tel)
        records = tel.ring.records
        for rec in records:
            obs_report.validate_record(rec)
        steps = tel.ring.steps()
        # 2 epochs x 3 batches (incl. the pad-masked tail), one-step-late
        assert len(steps) == 6
        assert opt.optim_method.state["neval"] == 7
        # THE canary: the whole ragged fit is exactly one compilation
        assert tel.compile_count == 1
        assert steps[-1]["compile_count"] == 1
        compiles = [r for r in records if r["type"] == "compile"]
        assert len(compiles) == 1 and compiles[0]["count"] == 1
        assert compiles[0]["seconds"] > 0

    def test_span_timings_nonempty_and_loss_matches_state(self):
        tel = Telemetry()
        opt = _fit_local(tel)
        steps = tel.ring.steps()
        seen = set()
        for s in steps:
            seen.update(s["spans"])
        assert "prefetch" in seen and "dispatch" in seen
        assert "pad_mask" in seen  # the ragged tail was padded, not dropped
        total = {k: 0.0 for k in ("prefetch", "dispatch")}
        for s in steps:
            for k in total:
                if k in s["spans"]:
                    total[k] += s["spans"][k]["s"]
        assert all(v > 0 for v in total.values())
        # the last flushed loss is the state's loss (one-step-late contract)
        assert steps[-1]["loss"] == pytest.approx(
            opt.optim_method.state["loss"]
        )

    def test_memory_stats_none_on_cpu(self):
        assert device_memory_stats() is None  # CPU backend: graceful None
        tel = Telemetry()
        _fit_local(tel, max_epoch=1)
        for s in tel.ring.steps():
            assert s["memory"] is None
            assert s["hbm_peak_bytes"] is None

    def test_exporter_fanout_agreement(self, tmp_path):
        """JSONL <-> ring buffer <-> TensorBoard must agree on loss/step for
        the same 2-epoch fit."""
        jpath = tmp_path / "events.jsonl"
        summary = TrainSummary(str(tmp_path), "obs_app")
        tel = Telemetry(
            exporters=[JsonlExporter(str(jpath)), SummaryExporter(summary)]
        )
        _fit_local(tel)
        tel.flush()
        ring_pairs = [(s["iteration"], s["loss"]) for s in tel.ring.steps()]
        with open(jpath) as fh:
            jrecs = [json.loads(l) for l in fh if l.strip()]
        json_pairs = [
            (r["iteration"], r["loss"]) for r in jrecs if r["type"] == "step"
        ]
        tb_pairs = summary.read_scalar("Loss")
        assert ring_pairs == json_pairs
        assert len(tb_pairs) == len(ring_pairs)
        for (ri, rl), (ti, tl) in zip(ring_pairs, tb_pairs):
            assert ri == ti
            assert tl == pytest.approx(rl, rel=1e-6)  # tfevents is float32
        # and the offline reporter renders the stream without error
        s = obs_report.summarize(obs_report.load(str(jpath)))
        assert s["n_steps"] == 6
        assert s["compile"]["count"] == 1
        assert "prefetch" in s["spans"] and "dispatch" in s["spans"]

    def test_tail_spans_drain_into_run_end_not_next_run(self):
        """Spans recorded after the last step record (final summary flush,
        end-of-run checkpoint) must land in the run_end meta record — not
        leak into a later run's first step."""
        tel = Telemetry()
        _fit_local(tel, max_epoch=1)
        run_end = [
            r for r in tel.ring.records
            if r["type"] == "meta" and r["event"] == "run_end"
        ][-1]
        # the last pending flush's summary span lands after the last step
        # (the epoch ran out, so it is part of that epoch's closing)
        assert "epoch_turnover/summary_flush" in run_end["spans"]
        tel2 = Telemetry()
        _fit_local(tel2, max_epoch=1)
        first = tel2.ring.steps()[0]["spans"]
        # run 1's tail did not leak: only seams of THIS run's warmup appear
        assert not [k for k in first if k.endswith("summary_flush")]
        assert "epoch_turnover" not in first

    def test_detached_fit_emits_nothing_and_collects_no_spans(self):
        from bigdl_tpu.obs import trace as obs_trace

        obs_trace.drain_aggregates()
        RandomGenerator.set_seed(7)
        x, y = _problem()
        opt = LocalOptimizer(_model(), _ragged_ds(x, y),
                             nn.ClassNLLCriterion())
        opt.set_end_when(Trigger.max_epoch(1))
        opt.optimize()
        # no active Telemetry run -> the span aggregator stays empty (the
        # detached hot loop pays no timing work beyond profiler annotations)
        assert obs_trace.peek_aggregates() == {}


# --------------------------------------------------------------------------
class TestCompileCanaryAllPaths:
    """Telemetry must report exactly 1 compile for a 2-epoch ragged fit on
    every execution path — the observable lock on PR 2's zero-recompile
    contract."""

    def test_distri_optimizer(self):
        from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer

        RandomGenerator.set_seed(29)
        x, y = _problem(n=64, d=6)
        ds = DataSet.distributed(DataSet.array(x, y, batch_size=16), 8)
        tel = Telemetry()
        opt = DistriOptimizer(_model(d=6), ds, nn.ClassNLLCriterion(),
                              parameter_sync="sharded")
        opt.set_optim_method(SGD(learningrate=0.2, momentum=0.9))
        opt.set_end_when(Trigger.max_epoch(2))
        opt.set_telemetry(tel)
        opt.optimize()
        assert tel.compile_count == 1
        steps = tel.ring.steps()
        assert steps and steps[-1]["path"] == "DistriOptimizer"
        assert steps[-1]["compile_count"] == 1
        for rec in tel.ring.records:
            obs_report.validate_record(rec)

    def test_hybrid_parallel_optimizer(self):
        from bigdl_tpu.parallel.hybrid import (
            HybridParallelOptimizer,
            make_mesh,
        )

        RandomGenerator.set_seed(7)
        x, y = _problem()
        mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
        tel = Telemetry()
        opt = HybridParallelOptimizer(
            _model(), _ragged_ds(x, y), nn.ClassNLLCriterion(), mesh=mesh
        )
        opt.set_optim_method(SGD(learningrate=0.2, momentum=0.9))
        opt.set_end_when(Trigger.max_epoch(2))
        opt.set_telemetry(tel)
        opt.optimize()
        assert tel.compile_count == 1  # ragged tail pad-masked, zero retrace
        assert opt.optim_method.state["neval"] == 7
        steps = tel.ring.steps()
        assert steps[-1]["path"] == "HybridParallelOptimizer"
        spans = set()
        for s in steps:
            spans.update(s["spans"])
        # the pjit batch-placement seam, nested under the prefetch span
        assert "prefetch/place_batch" in spans

    def test_predictor(self):
        RandomGenerator.set_seed(7)
        x, _ = _problem(n=20)
        model = _model()
        tel = Telemetry()
        pred = Predictor(model, batch_size=8, telemetry=tel)
        out = pred.predict(x)
        assert out.shape[0] == 20
        # chunks [8, 8, 4->padded 8]: one shape, ONE compile
        assert tel.compile_count == 1
        steps = tel.ring.steps()
        assert len(steps) == 3
        assert [s["records"] for s in steps] == [8, 8, 4]
        assert steps[0]["path"] == "Predictor"
        for rec in tel.ring.records:
            obs_report.validate_record(rec)
        # a second sweep through the same executable adds no compiles
        pred.predict(x)
        assert tel.compile_count == 1


# --------------------------------------------------------------------------
class TestHealthCanaryAllPaths:
    """PR 5 lock: `set_health` must NOT cost a recompile — with in-graph
    per-layer statistics enabled at stride 1, a 2-epoch ragged fit still
    compiles EXACTLY once on every execution path, and the health records
    pass the same schema gate as everything else."""

    def _assert_healthy_stream(self, tel):
        records = tel.ring.records
        for rec in records:
            obs_report.validate_record(rec)
        healths = [r for r in records if r["type"] == "health"]
        assert healths, "health enabled but no health records"
        assert healths[-1]["global"]["grad_norm"] > 0
        assert healths[-1]["global"]["nonfinite_grads"] == 0
        return healths

    def test_local_optimizer(self):
        from bigdl_tpu.obs import HealthConfig

        RandomGenerator.set_seed(7)
        x, y = _problem()
        tel = Telemetry()
        opt = LocalOptimizer(_model(), _ragged_ds(x, y),
                             nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learningrate=0.2, momentum=0.9))
        opt.set_end_when(Trigger.max_epoch(2))
        opt.set_telemetry(tel)
        opt.set_health(HealthConfig(every_n_steps=1, activations=True))
        opt.optimize()
        assert tel.compile_count == 1  # stats + activation hooks, 1 compile
        healths = self._assert_healthy_stream(tel)
        assert len(healths) == len(tel.ring.steps())  # stride 1
        assert "acts" in healths[-1]

    def test_distri_optimizer_sharded(self):
        from bigdl_tpu.obs import HealthConfig
        from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer

        RandomGenerator.set_seed(29)
        x, y = _problem(n=64, d=6)
        ds = DataSet.distributed(DataSet.array(x, y, batch_size=16), 8)
        tel = Telemetry()
        opt = DistriOptimizer(_model(d=6), ds, nn.ClassNLLCriterion(),
                              parameter_sync="sharded")
        opt.set_optim_method(SGD(learningrate=0.2, momentum=0.9))
        opt.set_end_when(Trigger.max_epoch(2))
        opt.set_telemetry(tel)
        opt.set_health(HealthConfig(every_n_steps=1))
        opt.optimize()
        assert tel.compile_count == 1  # segment stats ride the SPMD step
        healths = self._assert_healthy_stream(tel)
        # flat-codec rows name the same layer paths as the tree layout
        assert "Linear_0/weight" in healths[-1]["layers"]

    def test_hybrid_parallel_optimizer(self):
        from bigdl_tpu.obs import HealthConfig
        from bigdl_tpu.parallel.hybrid import (
            HybridParallelOptimizer,
            make_mesh,
        )

        RandomGenerator.set_seed(7)
        x, y = _problem()
        mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
        tel = Telemetry()
        opt = HybridParallelOptimizer(
            _model(), _ragged_ds(x, y), nn.ClassNLLCriterion(), mesh=mesh
        )
        opt.set_optim_method(SGD(learningrate=0.2, momentum=0.9))
        opt.set_end_when(Trigger.max_epoch(2))
        opt.set_telemetry(tel)
        opt.set_health(HealthConfig(every_n_steps=1))
        opt.optimize()
        assert tel.compile_count == 1
        self._assert_healthy_stream(tel)


class TestRunDirConvention:
    def _reset(self, engine):
        engine._state.run_dir = None

    def test_default_jsonl_under_run_dir(self, tmp_path):
        from bigdl_tpu.utils.engine import Engine

        old = Engine._state.run_dir
        try:
            Engine.set_run_dir(str(tmp_path / "run1"))
            tel = Telemetry()
            _fit_local(tel, max_epoch=1)
            tel.flush()
            # fleet naming: the default stream is per-process p<k>.jsonl so
            # N processes sharing one run dir never collide (PR 14); the old
            # events.jsonl name stays a read-compat alias in obs_report
            p = tmp_path / "run1" / "telemetry" / "p0.jsonl"
            assert p.exists()
            recs = obs_report.load(str(p))
            assert any(r["type"] == "step" for r in recs)
            # every record carries the fleet identity tag
            assert all(r["process_index"] == 0 for r in recs)
            assert all(r["process_count"] == 1 for r in recs)
            meta = [r for r in recs if r["type"] == "meta"][0]
            assert meta["run_dir"] == str(tmp_path / "run1")
        finally:
            Engine._state.run_dir = old

    def test_env_var_adopted(self, tmp_path, monkeypatch):
        from bigdl_tpu.utils.engine import Engine

        old = Engine._state.run_dir
        try:
            Engine._state.run_dir = None
            monkeypatch.setenv("BIGDL_RUN_DIR", str(tmp_path / "envrun"))
            assert Engine.run_dir() == str(tmp_path / "envrun")
            assert Engine.run_subdir("profile") == str(
                tmp_path / "envrun" / "profile"
            )
        finally:
            Engine._state.run_dir = old

    def test_set_profile_defaults_under_run_dir(self, tmp_path):
        from bigdl_tpu.utils.engine import Engine

        old = Engine._state.run_dir
        try:
            x, y = _problem()
            opt = LocalOptimizer(_model(), _ragged_ds(x, y),
                                 nn.ClassNLLCriterion())
            Engine._state.run_dir = None
            import os

            os.environ.pop("BIGDL_RUN_DIR", None)
            with pytest.raises(ValueError, match="run dir"):
                opt.set_profile()
            Engine.set_run_dir(str(tmp_path / "r"))
            opt.set_profile()
            assert opt._profile["dir"] == str(tmp_path / "r" / "profile")
        finally:
            Engine._state.run_dir = old

    def test_set_checkpoint_defaults_under_run_dir(self, tmp_path):
        from bigdl_tpu.utils.engine import Engine

        old = Engine._state.run_dir
        try:
            x, y = _problem()
            opt = LocalOptimizer(_model(), _ragged_ds(x, y),
                                 nn.ClassNLLCriterion())
            Engine.set_run_dir(str(tmp_path / "r2"))
            opt.set_checkpoint(trigger=Trigger.every_epoch())
            assert opt.checkpoint_path == str(tmp_path / "r2" / "checkpoints")
            with pytest.raises(ValueError, match="trigger"):
                opt.set_checkpoint(str(tmp_path))
        finally:
            Engine._state.run_dir = old


# --------------------------------------------------------------------------
class TestEstimatorTelemetry:
    def test_fit_streams_through_sklearn_surface(self):
        from bigdl_tpu.ml import DLClassifier

        RandomGenerator.set_seed(5)
        x, y = _problem(n=32, d=4)
        tel = Telemetry()
        est = DLClassifier(
            nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 3),
                          nn.LogSoftMax()),
            nn.ClassNLLCriterion(),
            batch_size=16,
            max_epoch=2,
            telemetry=tel,
        )
        est.fit(x, y)
        assert len(tel.ring.steps()) > 0
        assert tel.compile_count == 1
        assert "telemetry" in est.get_params()


# --------------------------------------------------------------------------
class TestObsReportTool:
    def test_selftest_passes(self):
        assert obs_report.selftest() == 0

    def test_bad_record_rejected(self):
        with pytest.raises(ValueError, match="lacks"):
            obs_report.validate_record({"type": "step", "ts": 1.0})
        with pytest.raises(ValueError, match="unknown record type"):
            obs_report.validate_record({"type": "nope", "ts": 1.0})
