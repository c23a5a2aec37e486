"""The host's step, named end to end (docs/observability.md, "The training
loop's spans"): a short attached fit over a dataset that takes 20 ms a batch,
on ``LocalOptimizer`` and on the ZeRO-1 ``DistriOptimizer`` over the CPU
mesh. Every span and the one counter of the catalogue must be in the step
records, the driver thread's top-level spans must close the step's wall, and
the seams the spans replaced (``dispatch_s``, ``input_wait_s``, the chaos
hook, the one compile) must read as before."""

import statistics
import time

import jax
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.dataset import LocalArrayDataSet
from bigdl_tpu.obs import Telemetry
from bigdl_tpu.obs import trace as obs_trace
from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger
from bigdl_tpu.resilience import FaultPlan
from bigdl_tpu.resilience.errors import FaultInjected
from bigdl_tpu.utils.random import RandomGenerator

BATCH, BATCHES, EPOCHS, DIM = 8, 6, 2, 6
# the driver thread's step, in the spans that are not nested under another
DRIVER = ("ring_wait", "dispatch", "loss_pull", "summary_flush",
          "epoch_turnover")


@pytest.fixture(scope="module", autouse=True)
def _engine_isolation():
    """The Distri fit freezes an 8-device Engine topology (tests/test_obs.py
    does the same): reset around the module."""
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    yield
    Engine.reset()


class _SlowDataSet(LocalArrayDataSet):
    """``DataSet.array`` whose every batch takes 20 ms to make: the step
    waits for data, as in a host-bound cell."""

    def data(self, train):
        for batch in super().data(train):
            time.sleep(0.02)
            yield batch


def _records():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((BATCH * BATCHES, DIM)).astype(np.float32)
    y = (np.arange(BATCH * BATCHES) % 3).astype(np.int32)
    return x, y


def _optimizer(kind):
    RandomGenerator.set_seed(11)
    x, y = _records()
    model = nn.Sequential(nn.Linear(DIM, 16), nn.Tanh(), nn.Linear(16, 3),
                          nn.LogSoftMax())
    ds = _SlowDataSet(x, y, batch_size=BATCH)
    if kind == "local":
        opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion())
    else:
        from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer

        opt = DistriOptimizer(model, DataSet.distributed(ds, BATCH),
                              nn.ClassNLLCriterion(), parameter_sync="sharded")
    opt.set_optim_method(SGD(learningrate=0.1, momentum=0.9))
    opt.set_end_when(Trigger.max_epoch(EPOCHS))
    return opt


@pytest.fixture(scope="module", params=["local", "zero1"])
def fit(request):
    """One attached two-epoch fit per optimizer, shared by the tests below."""
    tel = Telemetry()
    opt = _optimizer(request.param)
    opt.set_telemetry(tel)
    opt.optimize()
    return tel


def _total(steps, name, key):
    return sum(s["spans"].get(name, {}).get(key, 0) for s in steps)


def test_every_span_of_the_catalogue_is_in_the_step_records(fit):
    seen = {name for s in fit.ring.steps() for name in s["spans"]}
    assert seen >= {
        "ring_wait", "dataset_next", "prefetch", "dispatch",
        "dispatch/step_args", "dispatch/step_call", "dispatch/model_sync",
        "loss_pull", "summary_flush", "epoch_turnover",
        # the last step's flush falls inside the boundary
        "epoch_turnover/loss_pull", "epoch_turnover/summary_flush",
    }
    # the profiler sees the bare name, so no bare name has two seams
    bare = [name.rsplit("/", 1)[-1] for name in seen]
    assert {b for b in bare if bare.count(b) > 1} <= {
        "loss_pull", "summary_flush", "place_batch"}


def test_driver_spans_close_the_step_wall(fit):
    steps = fit.ring.steps()[2:]  # the compile and the step its flush delays
    share = [sum(s["spans"].get(n, {}).get("s", 0.0) for n in DRIVER)
             / s["wall_s"] for s in steps]
    assert 0.8 <= statistics.median(share) <= 1.02
    # and the step did wait for its data: that wait is the driver's, measured
    waits = [s["spans"]["ring_wait"]["s"] for s in steps
             if "dispatch" in s["spans"]]
    assert statistics.median(waits) > 0.01


def test_dispatch_is_its_three_parts(fit):
    share = []
    for s in fit.ring.steps()[1:]:
        if "dispatch" not in s["spans"]:
            continue  # an epoch's last flush: nothing was dispatched before it
        parts = sum(s["spans"]["dispatch/" + n]["s"]
                    for n in ("step_args", "step_call", "model_sync"))
        assert parts <= s["spans"]["dispatch"]["s"] + 3e-6  # three roundings
        share.append(parts / s["spans"]["dispatch"]["s"])
    assert statistics.median(share) >= 0.9


def test_one_epoch_turnover_per_boundary(fit):
    steps = fit.ring.steps()
    assert _total(steps, "epoch_turnover", "n") == EPOCHS - 1
    first_of_epoch_2 = next(s for s in steps if s["epoch"] == 2)
    assert first_of_epoch_2["spans"]["epoch_turnover"]["n"] == 1
    # the last epoch's closing is no boundary between epochs: it lands with
    # the run's other tail spans
    end = [r for r in fit.ring.records
           if r["type"] == "meta" and r.get("event") == "run_end"]
    assert end[-1]["spans"]["epoch_turnover"]["n"] == 1


def test_h2d_bytes_is_the_batch(fit):
    x, y = _records()
    want = x[:BATCH].nbytes + y[:BATCH].nbytes
    assert [s["h2d_bytes"] for s in fit.ring.steps()] == (
        [want] * (BATCHES * EPOCHS))


def test_old_fields_read_from_the_spans(fit):
    steps = fit.ring.steps()
    assert len(steps) == BATCHES * EPOCHS
    # dispatch_s is the dispatch span's seconds. The field names its own
    # step; the aggregate is drained one flush earlier (the wall-aligned one)
    for prev, cur in zip(steps, steps[1:]):
        agg = prev["spans"].get("dispatch")
        if agg is not None and agg["n"] == 1:
            assert cur["dispatch_s"] == pytest.approx(agg["s"], abs=1e-6)
    # input_wait_s is the worker's dataset_next: one pair of clock reads
    assert sum(s["input_wait_s"] for s in steps) == pytest.approx(
        _total(steps, "dataset_next", "s"), abs=1e-4)
    assert all(s["input_wait_s"] >= 0.019 for s in steps)
    # one dataset_next per batch and one more per epoch, for the StopIteration
    n = _total(steps, "dataset_next", "n") + sum(
        r["spans"].get("dataset_next", {}).get("n", 0)
        for r in fit.ring.records if r.get("event") == "run_end")
    assert n == (BATCHES + 1) * EPOCHS


def test_exactly_one_compile(fit):
    assert fit.compile_count == 1
    assert fit.ring.steps()[-1]["compile_count"] == 1


@pytest.mark.parametrize("kind", ["local", "zero1"])
def test_chaos_on_dispatch_fires_once_per_dispatch(kind):
    tel = Telemetry()
    opt = _optimizer(kind)
    opt.set_telemetry(tel)
    plan = FaultPlan(telemetry=tel).arm("dispatch", at_hit=4)
    with plan, pytest.raises(FaultInjected):
        opt.optimize()
    # the span is the seam: the fourth dispatch is the fourth hit, no more
    assert plan.hits("dispatch") == 4
    assert opt.optim_method.state["neval"] == 4
    assert [e["seam"] for e in plan.events] == ["dispatch"]


@pytest.mark.parametrize("kind", ["local", "zero1"])
def test_detached_fit_collects_nothing(kind):
    obs_trace.drain_aggregates()
    opt = _optimizer(kind)
    opt.optimize()  # no Telemetry: a span is its profiler annotation alone
    assert obs_trace.peek_aggregates() == {}
    assert obs_trace.current_collector() is None
    jax.block_until_ready(opt.model.get_parameters())
