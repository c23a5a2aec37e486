"""The benchmark's one command: one cell, one run.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``workloads/<cell>.json``. It names a configuration
(``configs/<name>.json`` + ``.py``), a traffic mix (``traffic/<mix>.json``,
whose ``generator`` names ``traffic/<generator>.py``), a driver
(``drivers/<kind>.py``) and the per-layer metrics it reports
(``layers/<metric>.py`` each). This file knows none of them by name: a later
cell, configuration, mix or metric is new files, never an edit here.

The last line of stdout is the result object; everything else is on earlier
lines, one JSON object each.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up starts here, before jax is imported

import argparse  # noqa: E402
import functools  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

KINDS = {"workloads": "cell", "configs": "configuration", "traffic": "traffic",
         "drivers": "driver", "layers": "per-layer metric"}


class Missing(LookupError):
    """A cell names a file that is not there."""


def _find(kind: str, name: str, ext: str, roots) -> str:
    for root in roots:
        path = os.path.join(root, kind, name + ext)
        if os.path.isfile(path):
            return path
    raise Missing(f"benchmark: no {KINDS[kind]} named {name!r} "
                  f"({kind}/{name}{ext} under {', '.join(roots)})")


def load_json(kind: str, name: str, roots) -> dict:
    with open(_find(kind, name, ".json", roots)) as f:
        return json.load(f)


def load_module(kind: str, name: str, roots):
    path = _find(kind, name, ".py", roots)
    mod_name = "benchmark_%s_%s" % (kind, name.replace(".", "_").replace("-", "_"))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, roots=(HERE,)) -> dict:
    """Everything a cell names, loaded by name; raises ``Missing`` with the
    name that is not there."""
    cell = load_json("workloads", name, roots)
    mix = load_json("traffic", cell["traffic"], roots)
    return {
        "cell": cell,
        "cfg": load_json("configs", cell["config"], roots),
        "config_module": load_module("configs", cell["config"], roots),
        "mix": mix,
        "generator": load_module("traffic", mix["generator"], roots),
        "driver": load_module("drivers", cell["driver"], roots),
        "readers": [load_module("layers", m, roots) for m in cell["per_layer"]],
    }


def _log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _read_trace(trace_dir: str):
    from benchmark.lib import trace, xplane

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise SystemExit(f"benchmark: the traced run left no .xplane.pb under "
                         f"{trace_dir} (the profiler failed to start?)")
    return trace.reduce_events(xplane.read_events(files[-1]))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t0: float, roots=(HERE,), rehearsal=None) -> dict:
    """Run one cell once and return the result object. ``rehearsal`` is for
    the CPU tests alone: ``{"platform", "device_kind", "reduced"}`` stand in
    for the chip and its trace; no command-line flag reaches it."""
    parts = load_cell(workload, roots)
    cell = parts["cell"]

    import jax

    from benchmark.lib import flops
    from benchmark.lib.peaks import peaks

    rehearsal = rehearsal or {}
    devices = jax.devices()
    platform = devices[0].platform
    if (platform != rehearsal.get("platform", "tpu")
            or len(devices) != cell["chips"]):
        raise SystemExit(
            f"benchmark: cell {workload} needs {cell['chips']} TPU chip(s); "
            f"jax found {len(devices)} device(s) of platform {platform!r}")
    kind = devices[0].device_kind

    scratch = os.path.join(ROOT, ".scratch", "benchmark")  # gitignored
    os.makedirs(scratch, exist_ok=True)
    run = parts["driver"].run(
        cell, parts["cfg"], parts["config_module"], parts["mix"],
        parts["generator"], seed=seed, seconds=seconds, trace=trace, t0=t0,
        chips=cell["chips"], scratch=scratch, on_tpu=platform == "tpu",
        log=_log)

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    breakdown = None
    if not trace:
        metrics = {k: {"value": v, "unit": run.units[k]}
                   for k, v in run.end_to_end.items()}
    else:
        run.trace = rehearsal.get("reduced") or _read_trace(run.trace_dir)
        run.peaks = peaks(rehearsal.get("device_kind", kind))
        run.log = _log

        @functools.cache
        def forward_costs():
            fn, args = run.forward()
            return flops.matmul_costs(fn, *args)

        run.forward_costs = forward_costs
        metrics = {}
        for reader in parts["readers"]:
            value = reader.read(run)
            if value is not None:
                metrics[reader.NAME] = {"value": value, "unit": reader.UNIT}
        r = run.trace
        device["busy_s"], device["window_s"] = r.busy_s, r.window_s
        ops = sorted(r.category_s.items(), key=lambda kv: -kv[1])
        breakdown = {
            "device_ops": [[c, s] for c, s in ops[:10]],
            "idle_gaps": [[name, s] for name, s in r.idle_gaps],
        }
    result = {"correct": bool(run.correct), "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    a = ap.parse_args()
    try:
        result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace), t0=T0)
    except Missing as e:
        raise SystemExit(str(e))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
