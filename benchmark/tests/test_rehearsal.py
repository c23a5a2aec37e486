"""The harness end to end on the CPU at a tiny size, and the manifest against
the files it names."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import run as bench
from benchmark.lib import trace

ROOT = bench.ROOT
FIXTURES = os.path.join(bench.HERE, "tests", "fixtures")
TINY = "tiny_resnet.tiny_hostfed"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name_and_agrees_with_the_manifest(cell):
    parts = bench.load_cell(cell)
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    for key in ("config", "traffic", "chips", "why"):
        assert parts["cell"][key] == entry[key]
    assert cell == f"{entry['config']}.{entry['traffic']}"
    config = next(c for c in MANIFEST["configs"] if c["name"] == entry["config"])
    assert parts["cfg"]["source"] == config["source"]
    assert parts["cfg"]["reduced"] == config["reduced"]
    assert config["file"] == f"benchmark/configs/{entry['config']}.json"
    declared = {m["name"] for m in MANIFEST["per_layer"]
                if cell in m.get("workloads", CELLS)}
    assert {r.NAME for r in parts["readers"]} == declared
    assert set(parts["driver"].END_TO_END) == {m["name"] for m in MANIFEST["end_to_end"]}


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_manifest_metric_is_its_reader(metric):
    reader = bench.load_module("layers", metric["name"], (bench.HERE,))
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        metric["name"], metric["unit"], metric["layer"], metric["moves"],
        metric["source"])
    assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}


def _rehearse(trace_on, **extra):
    rehearsal = {"platform": "cpu", **extra}
    return bench.run_cell(TINY, 2**31 + 77, 1.0, trace_on, t0=time.perf_counter(),
                          roots=(FIXTURES, bench.HERE), rehearsal=rehearsal)


def test_untraced_run_gives_the_contracts_object(capsys):
    result = _rehearse(False)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 10
    assert set(result["metrics"]) == {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["end_to_end"]:
        got = result["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(result)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert any("first_losses" in ln and "epoch_boundaries" in ln for ln in lines)


def test_traced_run_reports_every_per_layer_metric():
    r3 = trace.read_chrome_trace(os.path.join(
        ROOT, "bench_artifacts", "resnet50_b128_bf16act_s2d_trace.json.gz"))
    result = _rehearse(True, device_kind="TPU v5 lite",
                       reduced=trace.reduce_events(r3))
    declared = {m["name"]: m["unit"] for m in MANIFEST["per_layer"]}
    assert set(result["metrics"]) == set(declared)
    for name, got in result["metrics"].items():
        assert got["unit"] == declared[name] and got["value"] > 0
    assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
    assert len(result["breakdown"]["device_ops"]) == 10
    assert result["breakdown"]["device_ops"][0][0] == "convolution fusion"
    assert len(result["breakdown"]["idle_gaps"]) == 5
    json.dumps(result)


def test_traced_run_without_device_ops_fails():
    with pytest.raises(ValueError, match="no device op"):
        _rehearse(True, device_kind="TPU v5 lite")  # a CPU trace has no device plane


@pytest.mark.parametrize("key,kind", [("config", "configuration"),
                                      ("traffic", "traffic"),
                                      ("per_layer", "per-layer metric")])
def test_missing_file_is_named(tmp_path, key, kind):
    with open(os.path.join(FIXTURES, "workloads", TINY + ".json")) as f:
        cell = json.load(f)
    cell[key] = ["no_such_metric"] if key == "per_layer" else "no_such_thing"
    os.makedirs(tmp_path / "workloads")
    (tmp_path / "workloads" / "broken.json").write_text(json.dumps(cell))
    with pytest.raises(bench.Missing, match=f"no {kind} named 'no_such_"):
        bench.load_cell("broken", (str(tmp_path), FIXTURES, bench.HERE))
    with pytest.raises(bench.Missing, match="no cell named 'nowhere'"):
        bench.load_cell("nowhere")


def test_no_chip_no_number():
    with pytest.raises(SystemExit, match="needs 1 TPU chip"):
        bench.run_cell(CELLS[0], 1, 1.0, False, t0=time.perf_counter())
    out = subprocess.run(
        [sys.executable, os.path.join(bench.HERE, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_run_py_names_no_cell_configuration_or_metric():
    with open(os.path.join(bench.HERE, "run.py")) as f:
        source = f.read()
    names = CELLS + [c["name"] for c in MANIFEST["configs"]] + [
        m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert [n for n in names if n in source] == []
