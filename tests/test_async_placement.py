"""Device placement on the Distri path (docs/performance.md):

* the SPMD batch's sharding commit runs in the PREFETCH worker and the span
  data proves it — placement records as the nested ``prefetch/place_batch``
  span, never under the driver's ``dispatch`` seam;
* the hot-path invariants hold with it: exactly-1-compile ragged-free
  Distri fit, finite losses, health stream, and the chaos seam
  (``place_batch``) still fires inside the worker and recovers via the
  FailurePolicy;
* ``tools/obs_report.py``'s ``dispatch_gap_stats`` derived metric separates
  overlapped from serialized placement seconds.
"""

import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

from bigdl_tpu import nn, optim
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.obs import Telemetry
from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.random import RandomGenerator

REPO = Path(__file__).resolve().parent.parent
spec = importlib.util.spec_from_file_location(
    "obs_report", REPO / "tools" / "obs_report.py"
)
obs_report = importlib.util.module_from_spec(spec)
sys.modules[spec.name] = obs_report
spec.loader.exec_module(obs_report)


@pytest.fixture(scope="module", autouse=True)
def _engine():
    Engine.reset()
    Engine.init()
    yield
    Engine.reset()


def _fit(n=2048, feat=256, batch=256, epochs=3, sync="replicated"):
    RandomGenerator.set_seed(5)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, feat)).astype(np.float32)
    y = (np.arange(n) % 3).astype(np.int32)
    ds = DataSet.distributed(DataSet.array(x, y, batch_size=batch), 8)
    model = nn.Sequential(nn.Linear(feat, 64), nn.ReLU(), nn.Linear(64, 3),
                          nn.LogSoftMax())
    opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                          parameter_sync=sync)
    opt.set_optim_method(optim.SGD(learningrate=0.1))
    opt.set_end_when(optim.Trigger.max_epoch(epochs))
    tel = Telemetry()
    opt.set_telemetry(tel)
    opt.optimize()
    return opt, tel


def test_placement_overlaps_dispatch_span_proof():
    """THE acceptance lock: placement's span lands inside the prefetch
    worker (``prefetch/place_batch``) on every step, never inside the
    driver's dispatch seam, and the stream's derived metric counts it as
    overlapped."""
    _, tel = _fit()
    steps = tel.ring.steps()
    assert len(steps) == 24

    # structural proof: WHERE the placement span ran
    spans = {k for s in steps for k in s["spans"]}
    assert "prefetch/place_batch" in spans  # nested = worker thread
    assert not any(k.startswith("dispatch/") and k.endswith("place_batch")
                   for k in spans)  # nothing on the driver

    # the obs_report derived metric tells the same story from the stream
    g = obs_report.dispatch_gap_stats(steps)
    assert g["place_overlapped_s"] > 0
    assert g["place_serialized_s"] == 0


def test_prefetch_placement_one_compile_and_health():
    """Canary, extended: Distri ZeRO-1 sharded fit with worker-side
    placement + health — exactly one compile, finite losses, live health
    records."""
    opt, tel = _fit(n=512, feat=32, batch=64, epochs=2, sync="sharded")
    recs = tel.ring.records
    compiles = sum(r["count"] for r in recs if r["type"] == "compile")
    assert compiles == 1, f"placement recompiled: {compiles}"
    steps = tel.ring.steps()
    assert len(steps) == 16 and all(np.isfinite(s["loss"]) for s in steps)
    for r in recs:
        obs_report.validate_record(r)


def test_place_batch_chaos_seam_fires_and_recovers(tmp_path):
    """The new worker-side placement span is a chaos seam like any other:
    an armed fault fires from the prefetch thread, propagates to the
    driver, and the FailurePolicy recovers the run."""
    from bigdl_tpu.resilience import FailurePolicy, FaultPlan

    RandomGenerator.set_seed(13)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((64, 8)).astype(np.float32)
    y = (np.arange(64) % 3).astype(np.int32)
    ds = DataSet.distributed(DataSet.array(x, y, batch_size=8), 8)
    model = nn.Sequential(nn.Linear(8, 8), nn.Tanh(), nn.Linear(8, 3),
                          nn.LogSoftMax())
    opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                          parameter_sync="sharded")
    opt.set_optim_method(optim.SGD(learningrate=0.1))
    opt.set_end_when(optim.Trigger.max_iteration(10))
    opt.set_checkpoint(str(tmp_path), optim.Trigger.several_iteration(1))
    opt.set_failure_policy(FailurePolicy(backoff_base_s=0.0))
    tel = Telemetry()
    opt.set_telemetry(tel)
    plan = FaultPlan(telemetry=tel).arm("place_batch", at_hit=4)
    with plan:
        opt.optimize()
    assert any(e["seam"] == "place_batch" for e in plan.events)
    assert any(r["type"] == "retry" for r in tel.ring.records)
    assert opt.optim_method.state["neval"] >= 10


def test_dispatch_gap_stats_unit():
    """The derived metric's bucketing: the gap is the dispatch span alone —
    driver-thread placement is a sub-interval of it (reported as
    place_serialized_s, never added on top — that would double-count);
    worker-nested placement totals under place_overlapped_s."""
    steps = [
        {"wall_s": 0.1, "spans": {"dispatch": {"n": 1, "s": 0.01},
                                  "prefetch/place_batch": {"n": 1, "s": 0.04}}},
        # dispatch 0.06 CONTAINS the 0.05 serialized commit
        {"wall_s": 0.1, "spans": {"dispatch": {"n": 1, "s": 0.06},
                                  "place_batch": {"n": 1, "s": 0.05}}},
    ]
    g = obs_report.dispatch_gap_stats(steps)
    assert g["place_overlapped_s"] == 0.04
    assert g["place_serialized_s"] == 0.05
    assert g["p50_s"] == 0.01          # worker placement NOT in the gap
    assert g["max_s"] == 0.06          # the dispatch span, not 0.06 + 0.05
    assert obs_report.dispatch_gap_stats([]) is None
    # the same serialized commit as a stream names it since `dispatch` is a
    # span: nested under the driver's seam, not under the worker's
    nested = [{"wall_s": 0.1, "spans": {
        "dispatch": {"n": 1, "s": 0.06},
        "dispatch/step_args/place_batch": {"n": 1, "s": 0.05}}}]
    g = obs_report.dispatch_gap_stats(nested)
    assert (g["place_serialized_s"], g["place_overlapped_s"]) == (0.05, 0.0)
