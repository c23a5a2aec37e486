"""The two trace readers and the one reduction over their events."""

import glob
import gzip
import json
import os

import pytest

from benchmark.lib import trace, xplane
from benchmark.lib.trace import Event

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
R3 = os.path.join(ROOT, "bench_artifacts", "resnet50_b128_bf16act_s2d_trace.json.gz")

US = 1_000_000  # picoseconds


def test_committed_trace_reproduces_trace_analysis_r3():
    r = trace.reduce_events(trace.read_chrome_trace(R3), steady=False)
    assert (r.chips, r.steps) == (1, 5)
    per_step = lambda s: s / r.steps * 1e3  # noqa: E731
    assert per_step(r.busy_s) == pytest.approx(53.12, abs=0.005)
    assert per_step(r.category_s["convolution fusion"]) == pytest.approx(36.08, abs=0.005)
    assert per_step(r.category_s["loop fusion"]) == pytest.approx(10.75, abs=0.005)
    assert per_step(r.category_s["copy-done"]) == pytest.approx(3.03, abs=0.005)
    # on that trace no two ops overlap: the union equals the sum
    assert r.busy_s == pytest.approx(sum(r.category_s.values()), rel=1e-9)


def test_steady_window_is_whole_periods_of_the_step():
    r = trace.reduce_events(trace.read_chrome_trace(R3))
    assert r.steps == 3  # second execution's start to the last's start
    assert r.busy_s / r.steps * 1e3 == pytest.approx(53.12, abs=0.01)


def _hand_made():
    dev, host = "/device:TPU:0", "/host:CPU"
    return [
        # three device ops: the first two overlap by 2 us, then a 5 us gap,
        # the third, then nothing until the window's end
        Event(dev, "XLA Ops", "fusion.1", "convolution fusion", 10 * US, 6 * US),
        Event(dev, "XLA Ops", "fusion.2", "loop fusion", 14 * US, 4 * US),
        Event(dev, "XLA Ops", "copy-done.3", "copy-done", 23 * US, 2 * US),
        # two host spans: one covers the gap whole, a wider one covers it too
        Event(host, "MainThread", "summary_flush", None, 17 * US, 7 * US),
        Event(host, "MainThread", "train", None, 5 * US, 30 * US),
    ]


def test_busy_is_a_union_and_gaps_are_labelled():
    r = trace.reduce_events(_hand_made(), steady=False)
    assert r.busy_s == pytest.approx(10e-6)      # 10..18 and 23..25, not 12
    assert sum(r.category_s.values()) == pytest.approx(12e-6)
    assert r.window_s == pytest.approx(15e-6)    # 10..25
    assert r.idle_gaps == [("summary_flush", pytest.approx(5e-6))]


def test_gap_no_host_span_covers_is_unattributed():
    ev = [e for e in _hand_made() if trace.is_device(e.plane)]
    assert trace.reduce_events(ev, steady=False).idle_gaps[0][0] == "unattributed"


def test_trace_without_device_ops_is_an_error():
    with pytest.raises(ValueError, match="no device op"):
        trace.reduce_events([e for e in _hand_made() if not trace.is_device(e.plane)])


# ---- a protobuf writer just large enough for an XSpace ---------------------
def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _xspace(events, category_on_metadata=True):
    """The hand-made events as an XSpace: one plane per plane name, names and
    (for device ops) ``hlo_category`` on the event metadata, as the TPU
    profiler writes them."""
    planes = {}
    for e in events:
        planes.setdefault(e.plane, {}).setdefault(e.line, []).append(e)
    out = b""
    for pname, lines in planes.items():
        body = _field(2, pname)
        body += _field(5, _field(1, 1) + _field(2, _field(1, 1) + _field(2, "hlo_category")))
        meta_id = 0
        for lname, evs in lines.items():
            line = _field(2, lname) + _field(3, 1)  # timestamp_ns
            for e in evs:
                meta_id += 1
                stat = _field(1, 1) + _field(5, e.category) if e.category else b""
                meta = _field(1, meta_id) + _field(2, e.name)
                if stat and category_on_metadata:
                    meta += _field(5, stat)
                body += _field(4, _field(1, meta_id) + _field(2, meta))
                ev = (_field(1, meta_id) + _field(2, e.start_ps - 1000)
                      + _field(3, e.dur_ps))
                if stat and not category_on_metadata:
                    ev += _field(4, stat)
                line += _field(4, ev)
            body += _field(3, line)
        out += _field(1, body)
    return out


def _chrome(events):
    pids = {p: i + 1 for i, p in enumerate(dict.fromkeys(e.plane for e in events))}
    tids = {k: i + 1 for i, k in enumerate(dict.fromkeys((e.plane, e.line) for e in events))}
    raw = [{"ph": "M", "name": "process_name", "pid": i, "args": {"name": p}}
           for p, i in pids.items()]
    raw += [{"ph": "M", "name": "thread_name", "pid": pids[p], "tid": t,
             "args": {"name": ln}} for (p, ln), t in tids.items()]
    for e in events:
        x = {"ph": "X", "pid": pids[e.plane], "tid": tids[(e.plane, e.line)],
             "name": e.name, "ts": e.start_ps / US, "dur": e.dur_ps / US}
        if e.category:
            x["args"] = {"hlo_category": e.category}
        raw.append(x)
    return {"traceEvents": raw}


@pytest.mark.parametrize("on_metadata", [True, False])
def test_both_readers_give_the_same_events(tmp_path, on_metadata):
    events = _hand_made()
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(_xspace(events, on_metadata))
    js = tmp_path / "t.trace.json.gz"
    with gzip.open(js, "wt") as f:
        json.dump(_chrome(events), f)
    from_pb = xplane.read_events(str(pb))
    from_json = trace.read_chrome_trace(str(js))
    assert sorted(from_pb) == sorted(from_json) == sorted(events)
    assert trace.reduce_events(from_pb, steady=False) == \
        trace.reduce_events(from_json, steady=False)


def test_python_tracer_events_are_dropped(tmp_path):
    noise = Event("/host:CPU", "MainThread", "$run.py:1 main", None, 1 * US, 50 * US)
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(_xspace(_hand_made() + [noise]))
    assert noise not in xplane.read_events(str(pb))


def test_wire_reader_agrees_with_profile_data_on_a_cpu_capture(tmp_path):
    """jax's own reader and ours see the same events in a trace jax wrote."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for i in range(3):
        with jax.profiler.StepTraceAnnotation("train", step_num=i):
            with jax.profiler.TraceAnnotation("prefetch"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    ours = sorted((e.plane, e.line, e.name, e.start_ps, e.dur_ps)
                  for e in xplane.read_events(path))
    theirs = sorted(
        (p.name, ln.name, e.name, round(e.start_ns * 1000), round(e.duration_ns * 1000))
        for p in ProfileData.from_file(path).planes for ln in p.lines
        for e in ln.events if not e.name.startswith("$"))
    assert len(ours) == len(theirs) > 0
    assert {e[2] for e in ours} >= {"train", "prefetch"}
    for a, b in zip(ours, theirs):
        assert a[:3] == b[:3]
        assert abs(a[3] - b[3]) <= 1000 and abs(a[4] - b[4]) <= 1000  # float ns
