"""``lib/step_parts.py``'s reduction on a hand-made event list, the four
readers over it, what they give on a program without the scopes (nothing),
and the ``record_shards`` generator's round trip at a toy size."""

import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import run as bench
from benchmark.lib import step_parts, trace

FIXTURES = os.path.join(bench.HERE, "tests", "fixtures")
CELL = "tiny_resnet.tiny_filefed"
READERS = ["fwd_ms.train", "bwd_ms.train", "optim_update_ms.train",
           "unowned_ms.train"]
DEVICE = "/device:TPU:0"
STEP = "jit_train_step_s1(1)"
US = 1_000_000  # picoseconds


def _reader(name):
    return bench.load_module("layers", name, (bench.HERE,))


def _op(name, text, start_us, dur_us):
    return (DEVICE, trace.OPS_LINE, name, f"{name} {text}", start_us * US,
            dur_us * US)


def _step(t0):
    """One step of 100 us of ops in a period of 120 us, starting at ``t0``."""
    pre = "jit(train_step_s1)/jit(main)/"
    return [
        (DEVICE, trace.MODULES_LINE, STEP, STEP, t0 * US, 100 * US),
        _op("copy-done.1", "copy-done", t0, 4),
        _op("fusion.1", f'op_name="{pre}jvp(model_apply)/stem/stem_conv/'
            'conv_general_dilated"', t0 + 4, 20),
        # a fusion whose text holds two modules goes to the last
        _op("fusion.2", f'op_name="{pre}jvp(model_apply)/stem/stem_bn/mul" '
            f'long_name="{pre}jvp(model_apply)/stem/stem_relu/max"',
            t0 + 24, 6),
        _op("fusion.3", f'op_name="{pre}jvp(criterion)/jit(log_softmax)/'
            'reduce_max"', t0 + 30, 2),
        _op("fusion.4", f'op_name="{pre}transpose(jvp(criterion))/mul"',
            t0 + 32, 3),
        # under transpose(jvp(...)) is backward, wherever the part's name sits
        _op("fusion.5", f'op_name="{pre}transpose(jvp(model_apply))/stem/'
            'stem_conv/conv_general_dilated"', t0 + 35, 40),
        # a while holds its body's ops: it counts its own time only
        _op("while.1", f'op_name="{pre}transpose(jvp(model_apply))/rnn/while"',
            t0 + 75, 10),
        _op("fusion.6", f'op_name="{pre}transpose(jvp(model_apply))/rnn/'
            'while/body/add"', t0 + 76, 8),
        _op("fusion.7", f'op_name="{pre}optim_update/mul"', t0 + 85, 5),
        _op("all-reduce.1", f'op_name="{pre}state_sync/psum"', t0 + 90, 3),
        _op("fusion.8", "no name at all", t0 + 93, 7),
    ]


def _events(steps=4):
    return [e for k in range(steps) for e in _step(1000 + 120 * k)]


def test_owner_reads_part_direction_and_module():
    pre = "jit(train_step_s1)/jit(main)/"
    assert step_parts.owner(
        f'x op_name="{pre}jvp(model_apply)/res2a/res2a_b1/res2a_b1_conv/'
        'conv_general_dilated" y') == (
            "model_apply", False, "res2a/res2a_b1/res2a_b1_conv")
    assert step_parts.owner(
        f"{pre}transpose(jvp(model_apply))/layer_0/jvp(model_apply)/layer_0/"
        "checkpoint/rematted_computation/block/ssm/ssm_scan/while/body/"
        "closed_call") == ("model_apply", True, "layer_0/block/ssm/ssm_scan")
    assert step_parts.owner(f"{pre}jvp(model_apply)/fc/...i,oi->...o/"
                            "dot_general") == ("model_apply", False, "fc")
    assert step_parts.owner(f"{pre}optim_update/mul") == (
        "optim_update", False, "optim_update")
    assert step_parts.owner(f"{pre}transpose(jvp(param_views))/concatenate"
                            ) == ("param_views", True, "param_views")
    # a name inside a longer word is no name (the readers' boundary rule)
    assert step_parts.owner("fusion.3 a/my_criterion_x/criterions criterion"
                            ) == (None, False, "")
    # a source location is a file, not a scope: the text holds the op's stack
    assert step_parts.owner(
        "%fusion.9 = f32[8] fusion() /root/repo/bigdl_tpu/nn/criterion.py:113 "
        "/root/repo/bigdl_tpu/nn/criterion.py:41:15") == (None, False, "")
    assert step_parts.owner(
        f"/root/repo/bigdl_tpu/nn/criterion.py:41:15 {pre}jvp(model_apply)/"
        "head/dot_general: /x/criterion.py:3") == ("model_apply", False, "head")


def test_opcode_of_an_instruction_text():
    assert step_parts.opcode(
        "%copy-done.373 = f32[256]{0:T(256)} copy-done((f32[256]{0:T(256)}, "
        "f32[256]{0:T(256)S(1)}, u32[]{:S(2)}) %copy-start.373)") == "copy-done"
    assert step_parts.opcode(
        "%f.1 = (bf16[7]{0:T(256)(128)(2,1)S(1)}, s32[]) fusion(), kind=kLoop"
    ) == "fusion"
    assert step_parts.opcode("fusion.8") == "fusion.8"


def test_reduction_on_a_hand_made_event_list():
    t = step_parts.reduce(_events())
    assert t.steps == 2  # second execution's start to the last's
    ms = {p: [round(x * 1e6, 6) for x in v] for p, v in t.parts.items()}
    assert ms == {
        "unowned": [11.0, 0.0],          # the copy and the nameless fusion
        "model_apply": [26.0, 50.0],     # 20 + 6; 40 + (10 - 8) + 8
        "criterion": [2.0, 3.0],
        "optim_update": [5.0, 0.0],
        "state_sync": [3.0, 0.0],
    }
    modules = {p: [round(x * 1e6, 6) for x in v] for p, v in t.modules.items()}
    assert modules == {
        "stem/stem_conv": [20.0, 40.0],
        "stem/stem_relu": [6.0, 0.0],    # two modules in the text: the last
        "rnn": [0.0, 10.0],
        "criterion": [2.0, 3.0],
        "optim_update": [5.0, 0.0],
        "state_sync": [3.0, 0.0],
    }
    assert {n: round(v * 1e6, 6) for n, v in t.unowned_ops.items()} == {
        "copy-done.1": 4.0, "fusion.8": 7.0}
    assert t.unowned_kinds == t.unowned_ops  # bare names are their own kind
    # everything adds up to the window's busy time: 100 us a step
    assert sum(map(sum, t.parts.values())) == pytest.approx(100e-6)
    assert t.busy_s == pytest.approx(100e-6)
    reduced = trace.reduce_events(
        [trace.Event(p, line, n, None, s, d) for p, line, n, _, s, d in _events()])
    assert reduced.busy_s / reduced.steps == pytest.approx(t.busy_s)


def _run_over(events, monkeypatch):
    monkeypatch.setattr(step_parts, "read",
                        lambda trace_dir: step_parts.reduce(events))
    logged = []
    return SimpleNamespace(trace_dir="unused",
                           log=lambda **kw: logged.append(kw)), logged


@pytest.mark.parametrize("name,want", [
    ("fwd_ms.train", 0.028), ("bwd_ms.train", 0.053),
    ("optim_update_ms.train", 0.005), ("unowned_ms.train", 0.011)])
def test_reader_arithmetic(name, want, monkeypatch):
    run, _ = _run_over(_events(), monkeypatch)
    assert _reader(name).read(run) == pytest.approx(want)


def test_the_four_values_and_the_logged_table_add_up_to_the_busy_time(
        monkeypatch):
    run, logged = _run_over(_events(), monkeypatch)
    four = sum(_reader(n).read(run) for n in READERS)
    assert len(logged) == 1  # once a run, whichever reader comes first
    table = logged[0]["device_ms_per_step_by_part"]
    rest = sum(sum(v) for p, v in table.items()
               if p not in ("model_apply", "criterion", "optim_update",
                            "unowned"))
    assert four + rest == pytest.approx(logged[0]["device_busy_ms_per_step"])
    assert logged[0]["device_ms_per_step_by_module"][0] == [
        "stem/stem_conv", 0.02, 0.04]
    assert logged[0]["longest_unowned_ops"] == [["fusion.8", 0.007],
                                                ["copy-done.1", 0.004]]


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_in_a_program_without_the_scopes(
        name, monkeypatch):
    """The parent's step: every op's text is its old path; the reader
    returns None and does not raise, and nothing is logged."""
    old = [(p, line, n, "jit(train_step)/jit(main)/jvp(jit(relu))/max", s, d)
           for p, line, n, _, s, d in _events()]
    run, logged = _run_over(old, monkeypatch)
    assert _reader(name).read(run) is None and not logged
    assert _reader(name).read(SimpleNamespace(trace_dir=None, log=print)) is None


@pytest.mark.parametrize("name,layer", [
    ("fwd_ms.train", "jitted train step"), ("bwd_ms.train", "jitted train step"),
    ("optim_update_ms.train", "optimizer update"), ("unowned_ms.train", "device")])
def test_reader_says_what_the_manifest_will_need(name, layer):
    """No cell prints them yet (``PERF.md`` section 7): the constants are
    what a manifest entry has to repeat, checked against the manifest's own
    vocabulary."""
    import json

    with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    r = _reader(name)
    assert (r.NAME, r.UNIT, r.LAYER, r.SOURCE) == (
        name, "ms", layer, "device_trace")
    assert r.MOVES in {m["name"] for m in manifest["end_to_end"]}
    assert name not in {m["name"] for m in manifest["per_layer"]}


def test_record_shards_round_trip_with_the_seeds_labels():
    from bigdl_tpu.utils.random import RandomGenerator

    parts = bench.load_cell(CELL, (FIXTURES, bench.HERE))
    RandomGenerator.set_seed(7)
    seed = 2**31 + 11
    made = parts["generator"].make(parts["mix"], parts["cfg"], seed, 1)
    again = parts["generator"].make(parts["mix"], parts["cfg"], seed, 1)
    try:
        assert made.records == 32 and made.batch == 8
        assert made.steps_per_epoch == 4 and len(made.shards) == 4
        assert (made.labels == again.labels).all()
        rng = np.random.default_rng(seed)
        base = rng.integers(0, 256, (8, 32, 32, 3), np.uint8)
        offsets = rng.integers(0, 256, 32, np.uint8)
        want = {}
        for i in range(32):
            x = ((base[i % 8] + offsets[i]).astype(np.float32) / 255.0
                 - 0.449) / 0.226
            want[x.transpose(2, 0, 1).tobytes()] = int(made.labels[i])
        for epoch in range(2):
            made.dataset.shuffle()
            seen = 0
            for batch in made.dataset.data(train=True):
                x, y = np.asarray(batch.get_input()), batch.get_target()
                assert x.shape == (8, 3, 32, 32) and x.dtype == np.float32
                for row, label in zip(x, np.asarray(y)):
                    assert want[row.tobytes()] == int(label)
                    seen += 1
            assert seen == 32  # every record once an epoch
    finally:
        for m in (made, again):
            assert os.path.isdir(os.path.dirname(m.shards[0]))


def test_the_fixture_cell_runs_and_reports_nothing_off_the_chip():
    """A CPU capture has no device plane: the four readers find nothing to
    read and the line leaves them out (what the parent's program gives on
    the chip too); the run itself is fed from shards and is correct."""
    reduced = trace.reduce_events(_events_as_trace())
    result = bench.run_cell(
        CELL, 2**31 + 13, 1.0, True, t0=time.perf_counter(),
        roots=(FIXTURES, bench.HERE),
        rehearsal={"platform": "cpu", "device_kind": "TPU v5 lite",
                   "reduced": reduced})
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"] == {}


def _events_as_trace():
    return [trace.Event(p, line, n, None, s, d)
            for p, line, n, _, s, d in _events()]
