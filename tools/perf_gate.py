#!/usr/bin/env python
"""Perf regression gate + bench trajectory view (docs/performance.md).

Pure stdlib — no jax import — like ``tools/obs_report.py``: it runs in CI
and on any host that can read the artifacts. Three jobs:

* **Gate** — compare a measurement source against a committed baseline JSON
  (default ``PERF_BASELINE.json`` at the repo root) with per-metric
  tolerance bands, exiting non-zero on any regression. Sources:

  - a telemetry stream (``p<k>.jsonl`` or a run dir) — step walls, mean
    throughput, and the MFU series the always-on perf records carry;
  - a bench artifact (``BENCH_r*.json`` driver wrapper, or the raw
    ``bench.py`` headline JSON) — img/s/chip, MFU, step ms.

* **Trajectory** (``--trajectory``) — fold every ``BENCH_r*.json`` round
  plus the ``bench_artifacts/`` campaign files into ONE view of the
  img/s/chip / MFU series, with degraded/null rounds (timeouts, dead
  probes, rescue-mode headlines) explicitly flagged instead of silently
  missing — the empty-trajectory bug this tool closes.

* **Selftest** (``--selftest``) — CI gate over the checked-in artifacts:
  the trajectory must parse the committed rounds (r02/r03 numeric,
  r01/r04/r05 flagged), and the committed baseline must pass against the
  round it was cut from while failing against a seeded regression.

Usage::

    python tools/perf_gate.py <run>/telemetry/p0.jsonl     # gate a run
    python tools/perf_gate.py BENCH_r03.json               # gate a round
    python tools/perf_gate.py --baseline my_base.json run/ # custom baseline
    python tools/perf_gate.py --trajectory [--json]
    python tools/perf_gate.py --selftest
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(REPO, "PERF_BASELINE.json")

# stream-derived metric names (what a baseline may gate a telemetry run on)
STREAM_METRICS = ("step_ms", "records_per_sec", "mfu")
# bench-artifact metric names
BENCH_METRICS = ("img_per_sec_per_chip", "mfu", "step_ms")


def _obs_report():
    """Load the sibling obs_report module (schema validation + summary —
    one table of truth for the stream format)."""
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "obs_report.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault(spec.name, mod)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------- extraction
def metrics_from_summary(summary: Dict) -> Dict[str, float]:
    """Gateable metrics from an ``obs_report.summarize`` result."""
    out: Dict[str, float] = {}
    sw = summary.get("step_wall_s")
    if sw:
        out["step_ms"] = round(sw["p50"] * 1e3, 3)
    th = summary.get("throughput")
    if th:
        out["records_per_sec"] = th["mean"]
    perf = summary.get("perf")
    if perf and perf.get("mfu_mean") is not None:
        out["mfu"] = perf["mfu_mean"]
    return out


def metrics_from_bench(doc: Dict) -> Dict[str, float]:
    """Gateable metrics from a bench artifact: either the driver wrapper
    (``{"n": .., "rc": .., "parsed": {...}}``) or the raw headline JSON."""
    headline = doc.get("parsed") if "parsed" in doc else doc
    if not isinstance(headline, dict):
        return {}
    out: Dict[str, float] = {}
    if isinstance(headline.get("value"), (int, float)):
        out["img_per_sec_per_chip"] = float(headline["value"])
    m = headline.get("mfu_estimate")
    if m is None:
        m = headline.get("mfu")
    if isinstance(m, (int, float)):
        out["mfu"] = float(m)
    if isinstance(headline.get("step_ms"), (int, float)):
        out["step_ms"] = float(headline["step_ms"])
    return out


def measure(path: str) -> Dict[str, float]:
    """Resolve a measurement source: a ``.jsonl`` stream / run dir goes
    through obs_report (schema-validated), anything else is read as a bench
    artifact JSON."""
    if os.path.isdir(path) or path.endswith(".jsonl"):
        rep = _obs_report()
        records = rep.load(rep.resolve_stream(path))
        return metrics_from_summary(rep.summarize(records))
    with open(path, encoding="utf-8") as fh:
        return metrics_from_bench(json.load(fh))


# --------------------------------------------------------------------- gate
def load_baseline(path: str) -> Dict:
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    if not isinstance(doc.get("metrics"), dict) or not doc["metrics"]:
        raise ValueError(f"{path}: baseline needs a non-empty 'metrics' map")
    for name, m in doc["metrics"].items():
        if not isinstance(m.get("value"), (int, float)):
            raise ValueError(f"{path}: metric {name!r} needs a numeric value")
    return doc


def gate(measured: Dict[str, float], baseline: Dict,
         strict: bool = False) -> List[Dict]:
    """Per-metric verdicts: ``ok`` / ``improved`` (beyond tolerance in the
    good direction) / ``regression`` / ``missing`` (metric absent from the
    measurement — a failure only under ``strict``)."""
    rows: List[Dict] = []
    for name, spec in sorted(baseline["metrics"].items()):
        base = float(spec["value"])
        tol = float(spec.get("tolerance_pct", 10.0))
        higher = bool(spec.get("higher_is_better", True))
        got = measured.get(name)
        if got is None:
            rows.append({
                "metric": name, "baseline": base, "measured": None,
                "status": "regression" if strict else "missing",
                "note": "metric absent from the measurement",
            })
            continue
        band = base * tol / 100.0
        if higher:
            status = ("regression" if got < base - band
                      else "improved" if got > base + band else "ok")
        else:
            status = ("regression" if got > base + band
                      else "improved" if got < base - band else "ok")
        rows.append({
            "metric": name,
            "baseline": base,
            "measured": round(float(got), 6),
            "tolerance_pct": tol,
            "higher_is_better": higher,
            "delta_pct": round(100.0 * (float(got) - base) / base, 2),
            "status": status,
        })
    return rows


def render_gate(rows: List[Dict], baseline: Dict, source: str) -> str:
    lines = [
        "perf gate  vs %s (%s)"
        % (baseline.get("source", "baseline"), source)
    ]
    for r in rows:
        if r["measured"] is None:
            lines.append("  %-22s %-10s baseline %-10g (%s)"
                         % (r["metric"], r["status"].upper(), r["baseline"],
                            r["note"]))
            continue
        lines.append(
            "  %-22s %-10s measured %-12g baseline %-10g (%+.2f%%, "
            "band ±%g%%)"
            % (r["metric"], r["status"].upper(), r["measured"],
               r["baseline"], r["delta_pct"], r["tolerance_pct"])
        )
    return "\n".join(lines)


# --------------------------------------------------------------- trajectory
_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def load_trajectory(root: str = REPO) -> Dict:
    """Fold ``BENCH_r*.json`` rounds + ``bench_artifacts/`` campaign files
    into one trajectory structure. Every round appears — a timed-out or
    probe-dead round shows as a FLAGGED hole, never a silent gap."""
    rounds: List[Dict] = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        m = _ROUND_RE.search(os.path.basename(path))
        if not m:
            continue
        try:
            with open(path, encoding="utf-8") as fh:
                doc = json.load(fh)
        except (OSError, ValueError) as e:
            rounds.append({"round": int(m.group(1)), "status": "unreadable",
                           "note": str(e)})
            continue
        entry: Dict = {"round": int(m.group(1)), "rc": doc.get("rc")}
        headline = doc.get("parsed")
        metrics = metrics_from_bench(doc)
        if doc.get("rc") not in (0, None) and not metrics:
            entry["status"] = "null"
            entry["note"] = (
                "bench timed out (rc=124)" if doc.get("rc") == 124
                else f"bench exited rc={doc.get('rc')}"
            )
        elif not metrics or "img_per_sec_per_chip" not in metrics:
            entry["status"] = "null"
            entry["note"] = (
                (headline or {}).get("error")
                or "no numeric headline in this round"
            )
        else:
            entry.update(metrics)
            if isinstance(headline, dict) and (
                headline.get("degraded") or headline.get("error")
            ):
                entry["status"] = "degraded"
                entry["note"] = headline.get("error") or "degraded-mode rescue"
            else:
                entry["status"] = "ok"
            for key in ("device_kind", "metric"):
                if isinstance(headline, dict) and headline.get(key):
                    entry[key] = headline[key]
        rounds.append(entry)
    artifacts: List[Dict] = []
    art_dir = os.path.join(root, "bench_artifacts")
    if os.path.isdir(art_dir):
        for name in sorted(os.listdir(art_dir)):
            if not name.endswith(".json"):
                continue
            try:
                with open(os.path.join(art_dir, name), encoding="utf-8") as fh:
                    doc = json.load(fh)
            except (OSError, ValueError):
                artifacts.append({"name": name, "note": "unreadable"})
                continue
            row: Dict = {"name": name}
            if isinstance(doc, dict):
                for key in ("metric", "value", "unit", "backend",
                            "device_kind", "mfu", "mfu_estimate"):
                    if doc.get(key) is not None:
                        row[key] = doc[key]
            artifacts.append(row)
    numeric = [r for r in rounds if r["status"] in ("ok", "degraded")]
    holes = [r for r in rounds if r["status"] not in ("ok", "degraded")]
    return {
        "rounds": rounds,
        "artifacts": artifacts,
        "n_rounds": len(rounds),
        "n_numeric": len(numeric),
        "n_holes": len(holes),
        "best": (
            max(numeric, key=lambda r: r["img_per_sec_per_chip"])
            if numeric else None
        ),
    }


def render_trajectory(t: Dict) -> str:
    lines = [
        "bench trajectory  %d round(s): %d numeric, %d degraded/null hole(s)"
        % (t["n_rounds"], t["n_numeric"], t["n_holes"])
    ]
    lines.append("  round  img/s/chip   MFU      step_ms  status")
    for r in t["rounds"]:
        if r["status"] in ("ok", "degraded"):
            lines.append(
                "  r%02d    %-12g %-8s %-8s %s%s"
                % (
                    r["round"], r["img_per_sec_per_chip"],
                    "%.4f" % r["mfu"] if r.get("mfu") is not None else "-",
                    "%g" % r["step_ms"] if r.get("step_ms") is not None
                    else "-",
                    r["status"].upper() if r["status"] != "ok" else "ok",
                    f"  ({r['note']})" if r.get("note") else "",
                )
            )
        else:
            lines.append(
                "  r%02d    %-12s %-8s %-8s %s (%s)"
                % (r["round"], "—", "—", "—", r["status"].upper(),
                   r.get("note", "?"))
            )
    best = t.get("best")
    if best:
        lines.append(
            "  best: r%02d at %g img/s/chip (MFU %s) — campaign target "
            "MFU 0.40+"
            % (best["round"], best["img_per_sec_per_chip"],
               "%.4f" % best["mfu"] if best.get("mfu") is not None else "n/a")
        )
    if t["artifacts"]:
        lines.append("  campaign artifacts (bench_artifacts/):")
        for a in t["artifacts"]:
            detail = ", ".join(
                f"{k}={a[k]}" for k in ("value", "unit", "backend", "mfu")
                if a.get(k) is not None
            )
            lines.append("    %-36s %s" % (a["name"], detail or a.get(
                "note", "")))
    return "\n".join(lines)


# ----------------------------------------------------------------- selftest
def selftest() -> int:
    """CI gate over the checked-in artifacts: committed-round parsing, hole
    flagging, baseline pass, seeded-regression fail, tolerance edges, and
    stream-metric extraction from synthetic records."""
    failures: List[str] = []

    def expect(name: str, got, want) -> None:
        if got != want:
            failures.append(f"{name}: expected {want!r}, got {got!r}")

    # committed-history assertions only: rounds 2-5 are frozen artifacts, so
    # their values/statuses are exact; counts and "best" use INVARIANTS
    # (>=, not ==) so the next TPU campaign committing BENCH_r06.json (or
    # beating r03) cannot break every check.sh run
    t = load_trajectory(REPO)
    by_round = {r["round"]: r for r in t["rounds"]}
    expect("trajectory.n_rounds >= 4", t["n_rounds"] >= 4, True)
    expect("trajectory.r02.value", by_round[2].get("img_per_sec_per_chip"),
           1719.58)
    expect("trajectory.r02.mfu", by_round[2].get("mfu"), 0.2102)
    expect("trajectory.r03.value", by_round[3].get("img_per_sec_per_chip"),
           2265.57)
    expect("trajectory.r03.mfu", by_round[3].get("mfu"), 0.2807)
    expect("trajectory.r03.status", by_round[3]["status"], "ok")
    for hole in (4, 5):
        expect(f"trajectory.r0{hole}.flagged",
               by_round[hole]["status"] in ("null", "unreadable"), True)
    expect("trajectory.n_holes >= 2", t["n_holes"] >= 2, True)
    expect("trajectory.best exists and is >= r03",
           (t["best"] or {}).get("img_per_sec_per_chip", 0) >= 2265.57, True)

    baseline = load_baseline(DEFAULT_BASELINE)
    r03 = measure(os.path.join(REPO, "BENCH_r03.json"))
    rows = gate(r03, baseline)
    expect("gate.r03 passes",
           all(r["status"] in ("ok", "improved", "missing") for r in rows),
           True)
    seeded = dict(r03)
    seeded["img_per_sec_per_chip"] = r03["img_per_sec_per_chip"] * 0.8
    seeded["mfu"] = r03["mfu"] * 0.8
    rows = gate(seeded, baseline)
    expect("gate.seeded regression fails",
           sum(1 for r in rows if r["status"] == "regression") >= 2, True)
    # tolerance edges: exactly at the band passes, just beyond fails
    edge_base = {"metrics": {
        "m_hi": {"value": 100.0, "tolerance_pct": 10.0,
                 "higher_is_better": True},
        "m_lo": {"value": 100.0, "tolerance_pct": 10.0,
                 "higher_is_better": False},
    }}
    expect("gate.edge hi at band",
           gate({"m_hi": 90.0, "m_lo": 110.0}, edge_base)[0]["status"], "ok")
    expect("gate.edge hi beyond band",
           gate({"m_hi": 89.9, "m_lo": 100.0}, edge_base)[0]["status"],
           "regression")
    expect("gate.edge lo beyond band",
           gate({"m_hi": 100.0, "m_lo": 110.2}, edge_base)[1]["status"],
           "regression")
    expect("gate.missing is soft",
           gate({}, edge_base)[0]["status"], "missing")
    expect("gate.missing strict",
           gate({}, edge_base, strict=True)[0]["status"], "regression")

    # stream extraction from a synthetic summary (the obs_report golden
    # fixture is the schema gate; here only the metric mapping is at stake)
    summary = {
        "step_wall_s": {"p50": 0.0565},
        "throughput": {"mean": 2265.57},
        "perf": {"mfu_mean": 0.28},
    }
    expect("stream.metrics", metrics_from_summary(summary),
           {"step_ms": 56.5, "records_per_sec": 2265.57, "mfu": 0.28})

    if failures:
        print("perf_gate selftest FAILED:", file=sys.stderr)
        for f in failures:
            print("  " + f, file=sys.stderr)
        return 1
    # renderers must not crash on the live artifacts either
    render_trajectory(t)
    render_gate(gate(r03, baseline), baseline, "BENCH_r03.json")
    print(f"perf_gate selftest OK ({t['n_rounds']} rounds, "
          f"{len(baseline['metrics'])} baseline metrics)")
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("source", nargs="?",
                    help="telemetry p<k>.jsonl / run dir / bench artifact "
                         "JSON to gate")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline JSON (default: PERF_BASELINE.json)")
    ap.add_argument("--strict", action="store_true",
                    help="a baseline metric absent from the measurement "
                         "counts as a regression")
    ap.add_argument("--trajectory", action="store_true",
                    help="render the BENCH_r* + bench_artifacts trajectory")
    ap.add_argument("--root", default=REPO,
                    help="repo root holding BENCH_r*.json (trajectory mode)")
    ap.add_argument("--json", action="store_true", help="machine-readable")
    ap.add_argument("--selftest", action="store_true",
                    help="CI gate over the checked-in artifacts")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if args.trajectory:
        t = load_trajectory(args.root)
        print(json.dumps(t, indent=1) if args.json else render_trajectory(t))
        return 0
    if not args.source:
        ap.error("need a measurement source (or --trajectory / --selftest)")
    baseline = load_baseline(args.baseline)
    measured = measure(args.source)
    rows = gate(measured, baseline, strict=args.strict)
    if args.json:
        print(json.dumps({"source": args.source, "rows": rows}, indent=1))
    else:
        print(render_gate(rows, baseline, args.source))
    regressed = [r for r in rows if r["status"] == "regression"]
    if regressed:
        print(
            "PERF GATE FAILED: %d regressed metric(s): %s"
            % (len(regressed), ", ".join(r["metric"] for r in regressed)),
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
