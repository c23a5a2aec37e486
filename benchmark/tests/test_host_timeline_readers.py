"""The seven readers of the host's timeline (``ring_wait_ms.train`` …
``epoch_turnover_ms.train``): positive numbers from a real run of the
trainer on the CPU, the arithmetic on hand-made step records, and ``None``
on records of a program that has no such span (the parent commit's)."""

import os
import time
from types import SimpleNamespace

import pytest

from benchmark import run as bench
from benchmark.lib import trace

FIXTURES = os.path.join(bench.HERE, "tests", "fixtures")
CELL = "tiny_resnet.tiny_timeline"
READERS = ["ring_wait_ms.train", "ring_wait_ms_p95.train", "input_gbps.train",
           "host_step_ms.train", "step_call_ms.train", "model_sync_ms.train",
           "epoch_turnover_ms.train"]


def _reader(name):
    return bench.load_module("layers", name, (bench.HERE,))


def test_every_reader_gives_a_positive_number_on_a_real_run():
    r3 = trace.read_chrome_trace(os.path.join(
        bench.ROOT, "bench_artifacts", "resnet50_b128_bf16act_s2d_trace.json.gz"))
    result = bench.run_cell(
        CELL, 2**31 + 5, 1.0, True, t0=time.perf_counter(),
        roots=(FIXTURES, bench.HERE),
        rehearsal={"platform": "cpu", "device_kind": "TPU v5 lite",
                   "reduced": trace.reduce_events(r3)})
    assert result["correct"] is True
    assert list(result["metrics"]) == READERS
    for name, got in result["metrics"].items():
        assert got["unit"] == _reader(name).UNIT and got["value"] > 0, name


def _span(s, n=1):
    return {"n": n, "s": s}


def _hand_made():
    """Four steps of 100 ms; the third crosses an epoch boundary."""
    def step(ring, pull, **more):
        spans = {"ring_wait": _span(ring), "loss_pull": _span(pull),
                 "dataset_next": _span(0.05), "dispatch": _span(0.02),
                 "dispatch/step_args": _span(0.004),
                 "dispatch/step_call": _span(0.010),
                 "dispatch/model_sync": _span(0.006)}
        spans.update(more)
        return {"type": "step", "h2d_bytes": 100_000_000, "spans": spans}

    steps = [step(0.010, 0.060), step(0.020, 0.050),
             step(0.070, 0.001, epoch_turnover=_span(0.009)),
             step(0.030, 0.040)]
    return SimpleNamespace(steps=steps, walls=[0.1, 0.1, 0.1, 0.1])


@pytest.mark.parametrize("name,want", [
    ("ring_wait_ms.train", 25.0),        # median of 10, 20, 70, 30
    ("ring_wait_ms_p95.train", 70.0),    # nearest rank: the largest of four
    ("input_gbps.train", 2.0),           # 4 x 100 MB over 4 x 50 ms
    ("host_step_ms.train", 30.0),        # 100 less the two waits: 30 30 29 30
    ("step_call_ms.train", 10.0),
    ("model_sync_ms.train", 6.0),
    ("epoch_turnover_ms.train", 9.0),    # one boundary in the window
])
def test_reader_arithmetic(name, want):
    assert _reader(name).read(_hand_made()) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_on_the_parents_records(name):
    """The parent's step records: ``prefetch`` and the hand-timed ``dispatch``
    sample, no ``h2d_bytes``. The reader returns None and does not raise."""
    old = {"type": "step", "dispatch_s": 0.02, "input_wait_s": 0.08,
           "spans": {"prefetch": _span(0.006), "dispatch": _span(0.02),
                     "summary_flush": _span(0.001)}}
    run = SimpleNamespace(steps=[old, dict(old), {"type": "step"}],
                          walls=[0.1, 0.1, 0.1])
    assert _reader(name).read(run) is None
    assert _reader(name).read(SimpleNamespace(steps=[], walls=[])) is None


def test_no_boundary_in_the_window_is_no_number():
    run = _hand_made()
    del run.steps[2]["spans"]["epoch_turnover"]
    assert _reader("epoch_turnover_ms.train").read(run) is None
