"""Performance observability family (bigdl_tpu.obs.perf): cost-model math
units, schema-valid always-on perf streams on Local/Distri/Hybrid, the
1-compile canary with perf accounting on, the direct-driven PerfMonitor
matrix (breach / once-per-episode / re-arm / component attribution),
chaos-``delay``-driven profiler capture end-to-end on CPU, serving
bucket-cost stamping."""

import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.dataset import LocalArrayDataSet, SampleToMiniBatch
from bigdl_tpu.obs import Telemetry
from bigdl_tpu.obs.perf import (
    PerfAccountant,
    PerfConfig,
    PerfMonitor,
    classify_roofline,
    mfu,
    program_cost,
)
from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger
from bigdl_tpu.resilience import FaultPlan
from bigdl_tpu.utils.compat import device_peaks
from bigdl_tpu.utils.random import RandomGenerator

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module", autouse=True)
def _engine_isolation():
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    yield
    Engine.reset()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "tools" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


obs_report = _load_tool("obs_report")


def _problem(n=20, d=5, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.integers(0, classes, n)
    return x, y


def _model(d=5, classes=3):
    return nn.Sequential(
        nn.Linear(d, 16), nn.Tanh(), nn.Linear(16, classes), nn.LogSoftMax()
    )


def _ds(x, y, batch=8):
    return LocalArrayDataSet(
        x, y, transformer=SampleToMiniBatch(batch), batch_size=batch
    )


def _perf_cfg(**kw):
    base = dict(every_n_steps=2, baseline_steps=2, window=2, capture=False)
    base.update(kw)
    return PerfConfig(**base)


def _fit_local(tel, cfg=None, max_epoch=2, n=20):
    RandomGenerator.set_seed(7)
    x, y = _problem(n=n)
    opt = LocalOptimizer(_model(), _ds(x, y), nn.ClassNLLCriterion())
    opt.set_optim_method(SGD(learningrate=0.2, momentum=0.9))
    opt.set_end_when(Trigger.max_epoch(max_epoch))
    opt.set_telemetry(tel)
    if cfg is not None:
        opt.set_perf(cfg)
    opt.optimize()
    return opt


# ---------------------------------------------------------------------------
class TestCostModelMath:
    def test_mfu(self):
        # 1e12 flops in 0.5s on a 197 TFLOP/s chip (rounded to 6 places)
        assert mfu(1e12, 0.5, 197e12) == pytest.approx(
            2e12 / 197e12, abs=5e-7
        )
        assert mfu(1e12, 0.5, 197e12, n_devices=4) == pytest.approx(
            2e12 / (4 * 197e12), abs=5e-7
        )
        assert mfu(None, 0.5, 197e12) is None
        assert mfu(1e12, None, 197e12) is None
        assert mfu(1e12, 0.0, 197e12) is None
        assert mfu(1e12, 0.5, None) is None  # CPU: no peak entry

    def test_classify_roofline(self):
        # v5e-ish: ridge = 197e12 / 819e9 ≈ 240 flops/byte
        assert classify_roofline(500.0, 197e12, 819e9) == "compute"
        assert classify_roofline(50.0, 197e12, 819e9) == "bandwidth"
        assert classify_roofline(None, 197e12, 819e9) is None
        assert classify_roofline(50.0, None, 819e9) is None

    def test_device_peaks_table(self):
        v5e = device_peaks("TPU v5 lite")
        assert v5e is not None and v5e.flops == pytest.approx(197e12)
        assert v5e.hbm_bytes_s and v5e.ici_bytes_s
        v5p = device_peaks("TPU v5p")  # longest-substring match beats "v5"
        assert v5p.flops == pytest.approx(459e12)
        assert device_peaks("cpu") is None
        # the active CPU backend resolves to no peak entry
        assert device_peaks() is None

    def test_program_cost_on_tiny_jit(self):
        fn = jax.jit(lambda a, b: a @ b)
        spec = jax.ShapeDtypeStruct((32, 32), jnp.float32)
        cost = program_cost(fn, (spec, spec))
        assert cost is not None
        assert cost.flops and cost.flops > 0
        assert cost.bytes_accessed and cost.bytes_accessed > 0
        assert cost.arithmetic_intensity == pytest.approx(
            cost.flops / cost.bytes_accessed, rel=1e-3
        )
        assert not cost.collective_bytes  # no collectives in a local matmul


# ---------------------------------------------------------------------------
class TestLivePerfStreams:
    """Always-on accounting: every training path stamps its step records
    with cost-model-backed fields and emits schema-valid perf records —
    with the 1-compile canary still green."""

    def _assert_perf_stream(self, tel, expect_steps=None):
        records = tel.ring.records
        for rec in records:
            obs_report.validate_record(rec)
        steps = tel.ring.steps()
        if expect_steps is not None:
            assert len(steps) == expect_steps
        # every step record carries the cost-model stamps (mfu None on CPU)
        for s in steps:
            assert s.get("model_flops"), s
            assert s.get("achieved_flops_s") and s["achieved_flops_s"] > 0
            assert s.get("mfu") is None  # no CPU peak entry — None-graceful
        perfs = [r for r in records if r["type"] == "perf"]
        assert perfs, "no perf records with accounting on"
        for p in perfs:
            assert p["window"] >= 1
            bd = p["breakdown"]
            assert set(bd) == {"compute_s", "comms_s", "input_s", "host_s"}
            assert bd["compute_s"] >= 0
            assert p["model_flops"] and p["achieved_flops_s"]
            assert p["mfu"] is None and p["bound"] is None  # CPU
        assert tel.compile_count == 1  # the canary holds with perf on
        return perfs

    def test_the_cost_models_lowering_is_the_steps_own(self):
        """``program_cost`` lowers the step from the specs captured at its
        first dispatch. They carry the arguments' weak types, so that
        lowering is the dispatch's own: no second trace, no second MLIR
        module and, where the compiled cost model is asked (a TPU has no
        pre-compile one), no second load of the executable: on a language
        model those were 5 and 3 - 10 s of every launch (PERF.md, PR 35)."""
        traced = []

        class Counting(nn.ClassNLLCriterion):
            def counted(self, y, t):
                traced.append("counted")
                return super().counted(y, t)

            def unreduced(self, y, t):
                traced.append("unreduced")
                return super().unreduced(y, t)

        RandomGenerator.set_seed(7)
        x, y = _problem()
        tel = Telemetry()
        opt = LocalOptimizer(_model(), _ds(x, y), Counting())
        opt.set_optim_method(SGD(learningrate=0.2))
        opt.set_end_when(Trigger.max_epoch(1))
        opt.set_telemetry(tel)
        opt.optimize()
        assert all(s.get("model_flops") for s in tel.ring.steps())  # it ran
        assert len(traced) == 1, traced
        step, specs = opt._step_export_info
        lowered = step.lower(*specs)
        assert len(traced) == 1, traced

    def test_local_optimizer(self):
        tel = Telemetry()
        _fit_local(tel, _perf_cfg())
        perfs = self._assert_perf_stream(tel, expect_steps=6)
        assert len(perfs) == 3  # stride 2 over 6 steps

    def test_distri_optimizer_sharded(self):
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
        opt.set_perf(_perf_cfg())
        opt.optimize()
        perfs = self._assert_perf_stream(tel)
        # the SPMD program's collective bytes ride the perf record
        assert perfs[-1]["collective_bytes"], perfs[-1]

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
            _model(), _ds(x, y), nn.ClassNLLCriterion(), mesh=mesh
        )
        opt.set_optim_method(SGD(learningrate=0.2, momentum=0.9))
        opt.set_end_when(Trigger.max_epoch(2))
        opt.set_telemetry(tel)
        opt.set_perf(_perf_cfg())
        opt.optimize()
        self._assert_perf_stream(tel)

    def test_detached_fit_pays_nothing(self):
        """No telemetry -> no accounting: the accountant never lowers, the
        monitor never runs (mirrors the detached-fit contract of PR 3)."""
        RandomGenerator.set_seed(7)
        x, y = _problem()
        opt = LocalOptimizer(_model(), _ds(x, y), nn.ClassNLLCriterion())
        opt.set_end_when(Trigger.max_epoch(1))
        opt.set_perf(_perf_cfg())
        opt.optimize()
        assert opt._perf.cost is None  # never derived

    def test_set_perf_off(self):
        tel = Telemetry()
        RandomGenerator.set_seed(7)
        x, y = _problem()
        opt = LocalOptimizer(_model(), _ds(x, y), nn.ClassNLLCriterion())
        opt.set_end_when(Trigger.max_epoch(1))
        opt.set_telemetry(tel)
        opt.set_perf(False)
        opt.optimize()
        assert not [r for r in tel.ring.records if r["type"] == "perf"]
        assert all("model_flops" not in s for s in tel.ring.steps())


# ---------------------------------------------------------------------------
class TestPerfMonitor:
    """Direct-driven breach matrix: pure functions of the recorded samples —
    no thread, no sleeps, no real clock."""

    def _cfg(self, **kw):
        base = dict(baseline_steps=3, window=2, skip_steps=0,
                    slowdown_factor=1.5, capture=False)
        base.update(kw)
        return PerfConfig(**base)

    def _feed(self, pm, walls, start=1, mfus=None, comps=None):
        events = []
        for i, w in enumerate(walls):
            events.extend(pm.note_step(
                iteration=start + i, wall_s=w,
                mfu_value=None if mfus is None else mfus[i],
                breakdown=None if comps is None else comps[i],
            ))
        return events

    def test_breach_once_per_episode_and_rearm(self):
        pm = PerfMonitor(self._cfg())
        assert self._feed(pm, [0.1, 0.1, 0.1]) == []  # baseline
        assert self._feed(pm, [0.12, 0.12], start=4) == []  # within band
        evs = self._feed(pm, [0.3, 0.3], start=6)
        assert len(evs) == 1
        ev = evs[0]
        assert ev["reason"] == "perf_regression"
        assert ev["trigger"] == "step_time"
        # first slow step: window median blends (0.12, 0.3) -> 0.21
        assert ev["factor"] == pytest.approx(2.1)
        # still slow: once per episode, no repeat warn
        assert self._feed(pm, [0.3, 0.3, 0.3], start=8) == []
        # recovery re-arms ...
        assert self._feed(pm, [0.1, 0.1], start=11) == []
        # ... so a relapse warns again
        assert len(self._feed(pm, [0.4, 0.4], start=13)) == 1
        assert pm.event_count == 2

    def test_skip_steps_keeps_compile_wall_out_of_baseline(self):
        pm = PerfMonitor(self._cfg(skip_steps=1))
        # step 1 is the compile wall: 5s must not inflate the baseline
        self._feed(pm, [5.0, 0.1, 0.1, 0.1])
        assert pm.baseline_wall_s() == pytest.approx(0.1)

    def test_mfu_collapse_trigger(self):
        pm = PerfMonitor(self._cfg(mfu_collapse=0.5))
        # walls steady: only the MFU series degrades
        self._feed(pm, [0.1, 0.1, 0.1], mfus=[0.4, 0.4, 0.4])
        evs = self._feed(pm, [0.1, 0.1], start=4, mfus=[0.1, 0.1])
        assert len(evs) == 1
        assert evs[0]["trigger"] == "mfu_collapse"
        assert evs[0]["recent_mfu"] == pytest.approx(0.1)
        assert evs[0]["baseline_mfu"] == pytest.approx(0.4)

    def test_component_attribution(self):
        pm = PerfMonitor(self._cfg())
        fast = {"compute_s": 0.08, "comms_s": None, "input_s": 0.01,
                "host_s": 0.01}
        slow = {"compute_s": 0.08, "comms_s": None, "input_s": 0.21,
                "host_s": 0.01}
        self._feed(pm, [0.1, 0.1, 0.1], comps=[fast] * 3)
        evs = self._feed(pm, [0.3, 0.3], start=4, comps=[slow] * 2)
        assert len(evs) == 1
        assert evs[0]["component"] == "input"

    def test_poll_check_is_read_only_and_never_consumes_the_episode(self):
        """Regression (review finding): MonitorBase's poll thread calls
        check() and DISCARDS the result — a mutating check would silently
        latch the episode and the driver's note_step would never emit the
        warn/capture. check() must be a pure probe."""
        pm = PerfMonitor(self._cfg())
        self._feed(pm, [0.1, 0.1, 0.1])  # baseline
        self._feed(pm, [0.3], start=4)   # recent half-full: no evaluation
        # the poll races ahead of the driver: check() before the breach
        # sample must not fabricate or consume anything
        assert pm.check() == []
        evs = self._feed(pm, [0.3], start=5)  # the driver's breach event
        assert len(evs) == 1 and pm.event_count == 1
        # condition still holds: the poll probe SEES it without latching
        probe = pm.check()
        assert probe and probe[0]["trigger"] == "step_time"
        assert pm.check()  # repeatable — nothing consumed
        assert pm.event_count == 1  # only the driver's event counted
        # episode stays latched by the driver, not the poll
        assert self._feed(pm, [0.3], start=6) == []

    def test_config_validation(self):
        with pytest.raises(ValueError, match="slowdown_factor"):
            PerfConfig(slowdown_factor=0.9)
        with pytest.raises(ValueError, match="mfu_collapse"):
            PerfConfig(mfu_collapse=1.5)
        with pytest.raises(ValueError, match="every_n_steps"):
            PerfConfig(every_n_steps=0)


# ---------------------------------------------------------------------------
class TestTriggeredCapture:
    def test_chaos_delay_trips_monitor_and_captures_one_window(
        self, tmp_path
    ):
        """End-to-end on CPU: a chaos ``delay`` at the dispatch seam slows
        the run mid-fit; the PerfMonitor breaches once, blames the host
        component, emits ``warn reason=perf_regression``, and captures ONE
        bounded profiler window under <run_dir>/profile/."""
        from bigdl_tpu.utils.engine import Engine

        old = Engine._state.run_dir
        try:
            Engine.set_run_dir(str(tmp_path / "run"))
            tel = Telemetry()
            RandomGenerator.set_seed(7)
            x, y = _problem(n=64)
            opt = LocalOptimizer(_model(), _ds(x, y), nn.ClassNLLCriterion())
            opt.set_optim_method(SGD(learningrate=0.2, momentum=0.9))
            opt.set_end_when(Trigger.max_epoch(3))  # 8 batches x 3 epochs
            opt.set_telemetry(tel)
            opt.set_perf(PerfConfig(
                every_n_steps=4, baseline_steps=4, window=2, skip_steps=1,
                slowdown_factor=1.5, capture=True, capture_steps=2,
            ))
            plan = FaultPlan().arm(
                "dispatch", kind="delay", delay_s=0.25, at_hit=10, times=8
            )
            with plan:
                opt.optimize()
            assert len(plan.events) == 8
            warns = [r for r in tel.ring.records
                     if r["type"] == "warn"
                     and r["reason"] == "perf_regression"]
            assert len(warns) == 1  # once per episode
            ev = warns[0]
            assert ev["trigger"] == "step_time"
            # the injected delay lands in the driver dispatch seam
            assert ev["component"] == "host"
            cap = ev["capture_dir"]
            assert cap and cap.startswith(str(tmp_path / "run"))
            # the bounded window flushed a real trace to disk
            files = [p for p in Path(cap).rglob("*") if p.is_file()]
            assert files, f"no trace files under {cap}"
            # exactly one capture, and it was stopped (re-armed profiler)
            from bigdl_tpu.obs import perf as obs_perf

            assert opt._perf.captures == 1
            assert not obs_perf.capture_active()
        finally:
            Engine._state.run_dir = old


# ---------------------------------------------------------------------------
class TestServingBucketCost:
    def test_serve_records_carry_bucket_cost(self):
        from bigdl_tpu.serving import ModelServer

        RandomGenerator.set_seed(7)
        model = nn.Sequential(nn.Linear(12, 16), nn.ReLU(), nn.Linear(16, 4))
        model.init(sample_input=np.zeros((1, 12), np.float32))
        tel = Telemetry(exporters=[])
        with ModelServer(telemetry=tel) as srv:
            srv.register("m", model,
                         sample_input=np.zeros(12, np.float32),
                         batch_size=8, max_delay_ms=3)
            out = srv.predict("m", [np.ones(12, np.float32)] * 5)
            assert out.shape == (5, 4)
        serves = [r for r in tel.ring.records if r["type"] == "serve"]
        assert serves
        for s in serves:
            assert s.get("model_flops"), s  # per-flush padded-batch cost
            assert s.get("flops_per_record") == pytest.approx(
                s["model_flops"] / 8
            )
            assert "mfu" not in s or s["mfu"] is None  # CPU: no peak
        for rec in tel.ring.records:
            obs_report.validate_record(rec)
