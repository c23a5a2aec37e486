#!/usr/bin/env python
"""Summarize a telemetry JSONL stream (bigdl_tpu.obs) into a run report.

Pure stdlib — no jax import — so it runs instantly in CI and on any host that
can read the artifact. Input: the ``events.jsonl`` a
:class:`bigdl_tpu.obs.Telemetry` ``JsonlExporter`` wrote (schema:
``docs/observability.md``). Output: step-time percentiles, throughput trend,
HBM watermark, compile timeline, span breakdown, stall count.

Usage::

    python tools/obs_report.py <run>/telemetry/p0.jsonl
    python tools/obs_report.py <run_dir>              # resolves the stream
    python tools/obs_report.py p0.jsonl --json        # machine-readable
    python tools/obs_report.py --fleet <run_dir>      # merge N per-process
                                                      # streams (p*.jsonl) by
                                                      # (epoch, iteration)
    python tools/obs_report.py --selftest             # CI gate vs the
                                                      # checked-in golden
                                                      # fixtures

Fleet mode (docs/observability.md "fleet observability"): every process of a
multi-host run writes its own ``telemetry/p<k>.jsonl`` (the pre-fleet
single-process name ``events.jsonl`` is kept as a read-compat alias, loaded
as process 0). ``--fleet`` merges the streams BY (epoch, iteration) — never
by wall clock, which skews across hosts — rendering a per-host
step-time/throughput/input-wait table, aligned-step skew percentiles, the
straggler timeline from ``warn reason=straggler/host_lost`` records, and
per-replica serving health.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from typing import Dict, List, Optional, Sequence

# ---------------------------------------------------------------- schema
# Required fields per record type (docs/observability.md). Kept here — the
# tool is the validation gate — and exercised from tests/test_obs.py against
# live Telemetry output so tool and library cannot drift apart.
REQUIRED = {
    "step": ("iteration", "records", "wall_s", "compile_count", "spans"),
    "compile": ("iteration", "seconds", "count", "total_compiles"),
    "stall": ("waited_s", "deadline_s"),
    "meta": ("event",),
    # resilience runtime (docs/resilience.md)
    "retry": ("attempt", "fault_class"),
    "rollback": ("reason", "restored_step"),
    "fault_injected": ("seam", "kind"),
    "preempt_checkpoint": ("signal", "step"),
    # model health (obs/health.py): in-graph per-layer statistics pulled at
    # the one-step-late seam; "layers"/"acts" are optional (global-only mode)
    "health": ("iteration", "stride", "global"),
    # performance accounting (obs/perf.py): windowed compute/comms/input/
    # host decomposition + the cost-model join (model_flops / achieved /
    # mfu / roofline bound — each None-graceful where the backend reports
    # no cost model or peak entry)
    "perf": ("iteration", "window", "breakdown"),
    # advisory conditions (e.g. the update_ratio auto-LR guard, the serving
    # activation-drift monitor) that warrant operator attention but need no
    # recovery action
    "warn": ("reason",),
    # serving runtime (bigdl_tpu/serving): one record per continuous-batcher
    # flush — model/version, batch fill ratio, queue depth, SLO trigger that
    # fired, rolling end-to-end latency percentiles + requests/sec
    "serve": ("model", "iteration", "records", "batch_fill", "queue_depth"),
    # causal tracing (obs/trace.py): one id-bearing record per sampled (or
    # slow-promoted) span — trace/span/parent ids + duration. A flush span
    # additionally carries OpenTelemetry-style "links" to its member
    # request traces; a span's start time is ts - dur_s
    "span": ("name", "trace_id", "span_id", "dur_s"),
    # model warmup / AOT cold-start (docs/serving.md "fleet cold-start"):
    # one record per ModelServer warmup replay — wall seconds, traced
    # compiles, how many wrote FRESH persistent-cache entries (0 = the boot
    # was pure disk reads), and whether an artifact bundle drove it
    "warmup": ("model", "seconds", "compiles", "fresh_compiles",
               "warm_start"),
    # flight recorder (obs/blackbox.py): one record per sealed postmortem
    # bundle — the stream's LAST record on an abnormal exit names the
    # bundle that explains it (reason, path, dump latency, how many ring
    # types/records were frozen and how many older records the bounded
    # rings had already truncated)
    "postmortem": ("reason", "bundle", "dump_latency_s", "rings",
                   "records", "truncated"),
}

# every health "global" block carries the full five-channel summary
HEALTH_GLOBAL_KEYS = (
    "grad_norm", "weight_norm", "update_ratio",
    "nonfinite_grads", "nonfinite_params",
)


def validate_record(rec: Dict) -> None:
    """Raise ValueError when a record does not match the documented schema."""
    if not isinstance(rec, dict):
        raise ValueError(f"record is not an object: {rec!r}")
    rtype = rec.get("type")
    if rtype not in REQUIRED:
        raise ValueError(f"unknown record type {rtype!r}: {rec!r}")
    if "ts" not in rec:
        raise ValueError(f"record lacks ts timestamp: {rec!r}")
    missing = [k for k in REQUIRED[rtype] if k not in rec]
    if missing:
        raise ValueError(f"{rtype} record lacks {missing}: {rec!r}")
    if rtype == "step" and not isinstance(rec["spans"], dict):
        raise ValueError(f"step record spans must be an object: {rec!r}")
    if rtype == "span":
        if not isinstance(rec["dur_s"], (int, float)):
            raise ValueError(f"span record dur_s must be a number: {rec!r}")
        for id_key in ("trace_id", "span_id"):
            if not isinstance(rec[id_key], str) or not rec[id_key]:
                raise ValueError(
                    f"span record {id_key} must be a non-empty string: {rec!r}"
                )
        if "links" in rec and not isinstance(rec["links"], list):
            raise ValueError(f"span record links must be an array: {rec!r}")
    if rtype == "perf" and not isinstance(rec["breakdown"], dict):
        raise ValueError(f"perf record breakdown must be an object: {rec!r}")
    if rtype == "health":
        g = rec["global"]
        if not isinstance(g, dict):
            raise ValueError(f"health record global must be an object: {rec!r}")
        missing = [k for k in HEALTH_GLOBAL_KEYS if k not in g]
        if missing:
            raise ValueError(f"health record global lacks {missing}: {rec!r}")
        # optional blocks: per-layer rows, activation rows, comms-quantizer
        # telemetry (scale_amax/saturated/underflow — the low-precision
        # path), and GSPMD per-mesh-shard non-finite localization
        for opt_key in ("layers", "acts", "quant", "shards"):
            if opt_key in rec and rec[opt_key] is not None and not isinstance(
                rec[opt_key], dict
            ):
                raise ValueError(
                    f"health record {opt_key} must be an object: {rec!r}"
                )


def load(path: str) -> List[Dict]:
    records = []
    with open(path, encoding="utf-8") as fh:
        for lineno, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{lineno}: bad JSON: {e}") from e
            try:
                validate_record(rec)
            except ValueError as e:
                raise ValueError(f"{path}:{lineno}: {e}") from e
            records.append(rec)
    return records


def fleet_streams(path: str) -> Dict[int, str]:
    """Per-process stream files of a run dir, keyed by process index.

    Accepts the run dir itself, its ``telemetry/`` subdir, or any directory
    of JSONL streams. ``p<k>.jsonl`` names win; with none present, the
    pre-fleet single-process name ``events.jsonl`` is the read-compat alias
    (loaded as process 0)."""
    d = path
    tsub = os.path.join(path, "telemetry")
    if os.path.isdir(tsub):
        d = tsub
    if not os.path.isdir(d):
        raise ValueError(f"{path}: not a run directory (nor telemetry dir)")
    out: Dict[int, str] = {}
    for name in sorted(os.listdir(d)):
        if name.startswith("p") and name.endswith(".jsonl"):
            try:
                k = int(name[1:-6])
            except ValueError:
                continue
            out[k] = os.path.join(d, name)
    if not out:
        legacy = os.path.join(d, "events.jsonl")
        if os.path.exists(legacy):
            out[0] = legacy
    if not out:
        raise ValueError(
            f"{d}: no telemetry streams (p<k>.jsonl / events.jsonl) found"
        )
    return out


def resolve_stream(path: str) -> str:
    """Single-stream resolution for the non-fleet CLI: a file is itself; a
    directory resolves through :func:`fleet_streams` when it holds exactly
    one stream, and points at ``--fleet`` otherwise."""
    if os.path.isfile(path):
        return path
    streams = fleet_streams(path)
    if len(streams) == 1:
        return next(iter(streams.values()))
    raise ValueError(
        f"{path}: holds {len(streams)} per-process streams — use "
        "--fleet to merge them (or name one p<k>.jsonl explicitly)"
    )


# ---------------------------------------------------------------- summary
def percentile(sorted_vals: Sequence[float], p: float) -> float:
    """Nearest-rank percentile (deterministic, no interpolation)."""
    if not sorted_vals:
        raise ValueError("no values")
    import math

    rank = max(1, math.ceil(p / 100.0 * len(sorted_vals)))
    return sorted_vals[rank - 1]


def summarize(records: List[Dict]) -> Dict:
    steps = [r for r in records if r["type"] == "step"]
    compiles = [r for r in records if r["type"] == "compile"]
    stalls = [r for r in records if r["type"] == "stall"]
    retries = [r for r in records if r["type"] == "retry"]
    rollbacks = [r for r in records if r["type"] == "rollback"]
    faults = [r for r in records if r["type"] == "fault_injected"]
    preempts = [r for r in records if r["type"] == "preempt_checkpoint"]
    healths = [r for r in records if r["type"] == "health"]
    serves = [r for r in records if r["type"] == "serve"]
    warmups = [r for r in records if r["type"] == "warmup"]
    warns = [r for r in records if r["type"] == "warn"]
    perfs = [r for r in records if r["type"] == "perf"]
    span_recs = [r for r in records if r["type"] == "span"]

    by_class: Dict[str, int] = {}
    for r in retries:
        by_class[r["fault_class"]] = by_class.get(r["fault_class"], 0) + 1

    out: Dict = {
        "resilience": {
            "n_retries": len(retries),
            "retries_by_class": by_class,
            "n_rollbacks": len(rollbacks),
            "n_faults_injected": len(faults),
            "n_preempt_checkpoints": len(preempts),
        },
        "n_records": len(records),
        "n_steps": len(steps),
        "n_stalls": len(stalls),
        # >1 means the stream holds several run segments (one Telemetry
        # reused across fits, or appended files): per-run invariants like
        # the 1-compile canary must then be read per segment, not summed
        "n_runs": sum(
            1 for r in records
            if r["type"] == "meta" and r.get("event") == "run_start"
        ),
        "compile": {
            "count": sum(int(c["count"]) for c in compiles),
            "seconds": round(sum(float(c["seconds"]) for c in compiles), 6),
            # compiles served from the persistent cache as disk reads — on
            # an artifact warm boot EVERY compile record says cache_hit
            "cache_hits": sum(
                1 for c in compiles if c.get("cache_hit") is True
            ),
            "timeline": [
                {"iteration": c["iteration"], "seconds": c["seconds"]}
                for c in compiles
            ],
        },
    }

    walls = sorted(float(s["wall_s"]) for s in steps if s["wall_s"])
    if walls:
        out["step_wall_s"] = {
            "p50": percentile(walls, 50),
            "p90": percentile(walls, 90),
            "p99": percentile(walls, 99),
            "mean": round(sum(walls) / len(walls), 6),
            "max": walls[-1],
        }

    thr = [float(s["records_per_sec"]) for s in steps
           if s.get("records_per_sec")]
    if thr:
        q = max(1, len(thr) // 4)
        first, last = thr[:q], thr[-q:]
        out["throughput"] = {
            "mean": round(sum(thr) / len(thr), 3),
            "first_quarter_mean": round(sum(first) / len(first), 3),
            "last_quarter_mean": round(sum(last) / len(last), 3),
            # < 1.0 = the run slowed down over time (fragmentation, input
            # starvation, thermal); the trend turns "it got slower" into a
            # number without re-running anything
            "trend": round((sum(last) / len(last)) / (sum(first) / len(first)), 4),
        }

    peaks = [s["hbm_peak_bytes"] for s in steps
             if s.get("hbm_peak_bytes") is not None]
    out["hbm_peak_bytes"] = max(peaks) if peaks else None

    out["n_warns"] = len(warns)
    if warns:
        # reason breakdown: surfaces operational conditions an operator must
        # act on — e.g. "unwarmed_model" (first request pays the compile) or
        # "artifact_incompatible" (a replica booted cold despite a bundle)
        reasons: Dict[str, int] = {}
        for r in warns:
            reasons[r["reason"]] = reasons.get(r["reason"], 0) + 1
        out["warn_reasons"] = reasons
        unwarmed = sorted(
            {r.get("model") for r in warns
             if r["reason"] == "unwarmed_model" and r.get("model")}
        )
        if unwarmed:
            out["unwarmed_models"] = unwarmed
    if warmups:
        out["warmup"] = summarize_warmup(warmups)
    gap = dispatch_gap_stats(steps)
    if gap:
        out["dispatch_gap"] = gap
    ip = input_pipeline_stats(steps)
    if ip:
        out["input_pipeline"] = ip

    if perfs or any(s.get("model_flops") for s in steps):
        out["perf"] = summarize_perf(perfs, steps)

    if healths:
        out["health"] = summarize_health(healths, rollbacks)

    if serves:
        out["serving"] = summarize_serving(serves)

    sres = summarize_serving_resilience(serves, warns)
    if sres:
        out["serving_resilience"] = sres

    if span_recs:
        out["trace"] = summarize_trace(span_recs)

    postmortems = [r for r in records if r["type"] == "postmortem"]
    if postmortems:
        # the stream's postmortem records name the sealed bundles
        # (obs/blackbox.py) — on an abnormal exit the LAST record here is
        # the artifact tools/postmortem.py triages
        out["postmortem"] = {
            "n_dumps": len(postmortems),
            "reasons": [r["reason"] for r in postmortems],
            "bundles": [r["bundle"] for r in postmortems],
            "dump_latency_s_max": max(
                float(r["dump_latency_s"]) for r in postmortems),
            "rings_captured": postmortems[-1]["rings"],
            "records_captured": postmortems[-1]["records"],
            "truncated": postmortems[-1]["truncated"],
        }

    span_tot: Dict[str, Dict[str, float]] = {}
    for s in steps:
        for name, agg in s["spans"].items():
            t = span_tot.setdefault(name, {"n": 0, "s": 0.0})
            t["n"] += int(agg["n"])
            t["s"] += float(agg["s"])
    total_span_s = sum(t["s"] for t in span_tot.values()) or 1.0
    out["spans"] = {
        name: {
            "n": t["n"],
            "s": round(t["s"], 6),
            "pct": round(100.0 * t["s"] / total_span_s, 1),
        }
        for name, t in sorted(span_tot.items(), key=lambda kv: -kv[1]["s"])
    }
    return out


def dispatch_gap_stats(steps: List[Dict]) -> Optional[Dict]:
    """Span-overlap / dispatch-gap derived metric (docs/performance.md).

    Per step, the *dispatch gap* is the DRIVER-thread seam time spent getting
    the next step enqueued — the ``dispatch`` span, which is timed around the
    whole ``run_iteration`` call and therefore ALREADY CONTAINS any sharding
    commit that ran on the consumer thread (``dispatch/step_args/place_batch``
    — a bare ``place_batch`` in streams from before ``dispatch`` was a span —
    is a sub-interval of it, reported separately as ``place_serialized_s``,
    never added on top). Placement that ran in the prefetch worker instead
    nests under the worker's span (``prefetch/place_batch``) — it overlapped
    the in-flight step's compute, is no part of the gap, and totals under
    ``place_overlapped_s``. So "did the placement overlap dispatch" is
    answered by the span data alone: async placement moves seconds out of
    the gap and from ``place_serialized_s`` into ``place_overlapped_s``."""
    gaps = []
    overlapped = serialized = 0.0
    for s in steps:
        spans = s.get("spans") or {}
        v = spans.get("dispatch")
        gaps.append(round(float(v["s"]), 6) if v else 0.0)
        for name, v in spans.items():
            if name.rsplit("/", 1)[-1] != "place_batch":
                continue
            if name.startswith("prefetch/"):  # in the worker: overlapped
                overlapped += float(v["s"])
            else:  # on the driver, inside its dispatch seam
                serialized += float(v["s"])
    if not gaps:
        return None
    gs = sorted(gaps)
    return {
        "mean_s": round(sum(gaps) / len(gaps), 6),
        "p50_s": percentile(gs, 50),
        "max_s": gs[-1],
        "place_overlapped_s": round(overlapped, 6),
        "place_serialized_s": round(serialized, 6),
    }


def input_pipeline_stats(steps: List[Dict]) -> Optional[Dict]:
    """Host input-pipeline starvation derived metric (docs/performance.md),
    the analog of ``dispatch_gap`` for the seam UPSTREAM of the prefetcher.

    Per step, ``input_wait_s`` is the prefetch worker's wait for the next
    batch from the producing iterator — host time the input pipeline failed
    to stay ahead of the accelerator. ``input_starved_pct`` is the ratio of
    that wait to steady-state step wall (the first step is skipped: it
    absorbs pipeline spin-up and the compile). It can exceed 100%: the
    prefetcher waits AHEAD of the consumer (depth-N look-ahead), so on a
    fully input-bound run its accumulated wait overlaps more than one step
    interval — read ≈0 as "pipeline keeps up" and anything approaching or
    above 100 as "the input pipeline is the bottleneck".
    ``staging_depth_mean``
    averages the pipeline staging-ring depth sampled at each pull (a depth
    pinned at 0 while the starved pct is high = the transform chain, not the
    consumer, is the bottleneck — add workers)."""
    pairs = [
        (float(s["input_wait_s"]), float(s["wall_s"]))
        for s in steps[1:]
        if s.get("input_wait_s") is not None and s.get("wall_s")
    ]
    if not pairs:
        return None
    waits = sorted(w for w, _ in pairs)
    total_wait = sum(waits)
    total_wall = sum(w for _, w in pairs)
    depths = [
        int(s["input_qdepth"]) for s in steps[1:]
        if s.get("input_qdepth") is not None
    ]
    return {
        "p50_s": percentile(waits, 50),
        "mean_s": round(total_wait / len(waits), 6),
        "max_s": waits[-1],
        "input_starved_pct": (
            round(100.0 * total_wait / total_wall, 2) if total_wall else 0.0
        ),
        "staging_depth_mean": (
            round(sum(depths) / len(depths), 2) if depths else None
        ),
    }


PERF_COMPONENTS = ("compute_s", "comms_s", "input_s", "host_s")


def summarize_perf(perfs: List[Dict], steps: List[Dict]) -> Dict:
    """Performance-accounting section (obs/perf.py, docs/performance.md):
    the MFU series (perf records preferred, step-record stamps as the
    fallback), the latest cost-model join, and the mean compute/comms/
    input/host decomposition across the perf windows."""
    out: Dict = {"n_records": len(perfs)}
    mfus = [float(p["mfu"]) for p in perfs if p.get("mfu") is not None]
    if not mfus:
        mfus = [float(s["mfu"]) for s in steps if s.get("mfu") is not None]
    out["mfu_mean"] = round(sum(mfus) / len(mfus), 6) if mfus else None
    flops = [s.get("model_flops") for s in steps] + [
        p.get("model_flops") for p in perfs
    ]
    flops = [f for f in flops if f]
    out["model_flops"] = flops[-1] if flops else None
    if perfs:
        last = perfs[-1]
        out["last"] = {
            k: last.get(k)
            for k in ("iteration", "mfu", "achieved_flops_s", "wall_mean_s",
                      "arithmetic_intensity", "collective_bytes",
                      "all_to_all_bytes", "ppermute_bytes",
                      "pipe_bubble_frac")
        }
        out["bound"] = last.get("bound")
        comp: Dict[str, Optional[float]] = {}
        for key in PERF_COMPONENTS:
            vals = [
                p["breakdown"].get(key) for p in perfs
                if isinstance(p.get("breakdown"), dict)
            ]
            known = [v for v in vals if v is not None]
            comp[key] = round(sum(known) / len(known), 6) if known else None
        out["breakdown_mean"] = comp
    return out


def render_perf(p: Dict) -> List[str]:
    last = p.get("last") or {}
    lines = [
        "perf       %d record(s)  mfu %s%s  model-flops %s%s"
        % (
            p["n_records"],
            "%.4f" % p["mfu_mean"] if p["mfu_mean"] is not None
            else "n/a (no peak entry — CPU?)",
            "" if last.get("mfu") is None else "  (last %.4f)" % last["mfu"],
            "%.3g" % p["model_flops"] if p.get("model_flops") else "n/a",
            "  %s-bound (AI %.1f)"
            % (p["bound"], last["arithmetic_intensity"])
            if p.get("bound") and last.get("arithmetic_intensity") is not None
            else "",
        )
    ]
    # pp/ep observables (PR 17): the pipeline schedule's idle fraction and
    # the per-parallelism collective bytes, when the run's programs carry them
    extras = []
    if last.get("pipe_bubble_frac") is not None:
        extras.append("pipe-bubble %.3f" % last["pipe_bubble_frac"])
    if last.get("ppermute_bytes"):
        extras.append("ppermute %s B/step" % last["ppermute_bytes"])
    if last.get("all_to_all_bytes"):
        extras.append("all_to_all %s B/step" % last["all_to_all_bytes"])
    if extras:
        lines.append("  parallelism    " + "  ".join(extras))
    comp = p.get("breakdown_mean")
    if comp:
        wall = sum(v for v in comp.values() if v is not None) or None
        parts = []
        for key in PERF_COMPONENTS:
            v = comp.get(key)
            if v is None:
                parts.append("%s n/a" % key[:-2])
            else:
                pct = "" if not wall else " (%d%%)" % round(100.0 * v / wall)
                parts.append("%s %.2fms%s" % (key[:-2], v * 1e3, pct))
        lines.append("  decomposition  " + "  ".join(parts))
    return lines


def summarize_health(healths: List[Dict], rollbacks: List[Dict]) -> Dict:
    """Model-health section: trajectory of the global norms, the final
    per-layer table, and the first-nonfinite attribution timeline (rollback
    records carrying the layer/source a HealthMonitor named)."""
    last = healths[-1]
    gn = [float(h["global"]["grad_norm"]) for h in healths]
    ur = [float(h["global"]["update_ratio"]) for h in healths]
    finite_gn = [v for v in gn if v == v]  # NaN-safe max
    finite_ur = [v for v in ur if v == v]
    out: Dict = {
        "n_records": len(healths),
        "stride": last["stride"],
        "last_global": last["global"],
        "grad_norm_max": max(finite_gn) if finite_gn else None,
        "update_ratio_max": max(finite_ur) if finite_ur else None,
        # steps whose in-graph counters saw ANY non-finite grad/param — the
        # poisoned-step count even when no rollback fired (e.g. guard off)
        "nonfinite_steps": sum(
            1 for h in healths
            if h["global"]["nonfinite_grads"] or h["global"]["nonfinite_params"]
        ),
    }
    layers = last.get("layers")
    if layers:
        out["layers"] = layers
    acts = last.get("acts")
    if acts:
        out["acts"] = acts
    # attribution timeline: every rollback that named its poisoned layer
    out["attribution"] = [
        {
            "iteration": r.get("iteration"),
            "layer": r.get("layer"),
            "source": r.get("source"),
            "restored_step": r.get("restored_step"),
        }
        for r in rollbacks
        if r.get("layer") is not None or r.get("source") is not None
    ]
    return out


def summarize_warmup(warmups: List[Dict]) -> Dict:
    """Cold-start section (docs/serving.md "fleet cold-start"): per model
    the BOOT warmup's wall seconds, traced-compile count, fresh-entry count
    and warm-start flag, plus the boot headline — total seconds to
    all-models-ready and whether the whole boot was compile-free
    (``all_cache_hits``: every warmup wrote 0 fresh persistent-cache
    entries, the telemetry proof an artifact warm boot asserts on). The
    FIRST record per model is the boot; later ones are hot-swap warmups
    (counted as ``swap_warmups`` — a swap's cache-hot replay must not
    shadow what the actual boot cost)."""
    models: Dict[str, Dict] = {}
    for r in warmups:
        if r["model"] in models:
            models[r["model"]]["swap_warmups"] += 1
            continue
        models[r["model"]] = {
            "seconds": float(r["seconds"]),
            "compiles": int(r["compiles"]),
            "fresh_compiles": (
                None if r.get("fresh_compiles") is None
                else int(r["fresh_compiles"])
            ),
            "warm_start": bool(r.get("warm_start")),
            "buckets": r.get("buckets"),
            "version": r.get("version"),
            "swap_warmups": 0,
        }
    fresh = [m["fresh_compiles"] for m in models.values()]
    return {
        "models": models,
        "boot_to_ready_s": round(sum(m["seconds"] for m in models.values()), 6),
        "total_fresh_compiles": (
            None if any(f is None for f in fresh) else sum(fresh)
        ),
        "all_cache_hits": bool(fresh) and all(f == 0 for f in fresh),
        "warm_start": all(m["warm_start"] for m in models.values()),
    }


def render_warmup(w: Dict) -> List[str]:
    lines = [
        "cold start boot-to-ready %.3fs  fresh compiles %s  %s"
        % (
            w["boot_to_ready_s"],
            "n/a (no compile cache)" if w["total_fresh_compiles"] is None
            else w["total_fresh_compiles"],
            "[artifact warm start]" if w["warm_start"] else "[traced boot]",
        )
    ]
    for name, m in sorted(w["models"].items()):
        lines.append(
            "  %s v%s  warmup %.3fs  compiles %d  fresh %s%s%s%s"
            % (
                name, m["version"], m["seconds"], m["compiles"],
                "n/a" if m["fresh_compiles"] is None else m["fresh_compiles"],
                "  [warm]" if m["warm_start"] else "",
                f"  buckets {m['buckets']}" if m.get("buckets") else "",
                f"  (+{m['swap_warmups']} swap warmup(s))"
                if m.get("swap_warmups") else "",
            )
        )
    return lines


def summarize_serving(serves: List[Dict]) -> Dict:
    """Serving section: per-model flush/request totals, mean batch fill,
    trigger mix (how often the SLO delay bound fired vs a full batch), the
    latest rolling latency percentiles + requests/sec, and the buckets/
    versions actually exercised."""
    models: Dict[str, Dict] = {}
    for r in serves:
        m = models.setdefault(r["model"], {
            "flushes": 0, "requests": 0, "fill_sum": 0.0,
            "queue_depth_max": 0, "by_trigger": {}, "buckets": set(),
            "p50_ms": None, "p99_ms": None, "rps": None,
            "version": None, "quantized": None, "drift_samples": 0,
            "rejected": 0, "trace_id": None,
        })
        m["flushes"] += 1
        m["requests"] += int(r["records"])
        m["fill_sum"] += float(r["batch_fill"])
        m["queue_depth_max"] = max(m["queue_depth_max"], int(r["queue_depth"]))
        trg = r.get("trigger")
        if trg:
            m["by_trigger"][trg] = m["by_trigger"].get(trg, 0) + 1
        for k in ("p50_ms", "p99_ms", "rps"):
            if r.get(k) is not None:
                m[k] = r[k]  # latest rolling-window value wins
        if r.get("version") is not None:
            m["version"] = int(r["version"])
        if r.get("trace_id") is not None:
            # the slowest member request of the latest flush — the handle
            # an operator feeds to /trace?id= or tools/trace_export.py
            m["trace_id"] = r["trace_id"]
        if r.get("rejected") is not None:
            # cumulative admission-control reject count; latest wins
            m["rejected"] = int(r["rejected"])
        if r.get("quantized") is not None:
            # bool (legacy int8 tag) or a mode string ("int8" / "fp8")
            q = r["quantized"]
            m["quantized"] = q if isinstance(q, str) else bool(q)
        if r.get("bucket") is not None:
            m["buckets"].add(int(r["bucket"]))
        if r.get("drift") is not None:
            m["drift_samples"] += 1
    for m in models.values():
        m["mean_fill"] = round(m.pop("fill_sum") / m["flushes"], 4)
        m["buckets"] = sorted(m["buckets"])
    return {
        "n_flushes": len(serves),
        "n_requests": sum(int(r["records"]) for r in serves),
        "models": models,
    }


def summarize_serving_resilience(serves: List[Dict],
                                 warns: List[Dict]) -> Optional[Dict]:
    """Serving-resilience section (docs/serving.md "resilience"): per-model
    deadline-miss / swept-expired / breaker-shed counters (cumulative on
    serve records — latest wins), supervisor restart and wedge counts
    (``warn reason=worker_restart/worker_wedged``), and the breaker
    open/close timeline (``warn reason=circuit_open/circuit_closed`` in
    stream order). Returns None when the stream carries no resilience
    signal at all, so quiet runs stay quiet."""

    def entry(models: Dict, name) -> Dict:
        # warn records need no "model" field to be schema-valid; a missing
        # one must not mint a None key that later breaks sorted(...)
        return models.setdefault(name or "<unknown>", {
            "deadline_missed": 0, "swept_expired": 0, "shed": 0,
            "breaker_state": None, "restarts": 0, "wedges": 0,
        })

    models: Dict[str, Dict] = {}
    signal = False
    for r in serves:
        m = entry(models, r["model"])
        for k in ("deadline_missed", "swept_expired", "shed"):
            if r.get(k) is not None:
                m[k] = int(r[k])  # cumulative counter: latest wins
                signal = signal or m[k] > 0
        if r.get("breaker_state") is not None:
            m["breaker_state"] = r["breaker_state"]
            signal = signal or r["breaker_state"] != "closed"
    timeline: List[Dict] = []
    for w in warns:
        reason = w["reason"]
        if reason in ("circuit_open", "circuit_closed"):
            signal = True
            timeline.append({
                "model": w.get("model"),
                "event": reason,
                "cause": w.get("cause"),
                "ts": w.get("ts"),
            })
        elif reason in ("worker_restart", "worker_dead"):
            signal = True
            m = entry(models, w.get("model"))
            m["restarts"] = max(m["restarts"], int(w.get("restarts") or 0))
            if reason == "worker_dead":
                m["gave_up"] = True
        elif reason == "worker_wedged":
            signal = True
            entry(models, w.get("model"))["wedges"] += 1
        elif reason == "deadline_exceeded":
            signal = True
            m = entry(models, w.get("model"))
            # the sweep/flush-seam warns carry cumulative counters too —
            # keeps the numbers visible even when no serve record ever
            # follows (a model whose every request expires)
            if w.get("swept_expired") is not None:
                m["swept_expired"] = max(
                    m["swept_expired"], int(w["swept_expired"])
                )
            if w.get("deadline_missed") is not None:
                m["deadline_missed"] = max(
                    m["deadline_missed"], int(w["deadline_missed"])
                )
            m["deadline_missed"] = max(
                m["deadline_missed"], m["swept_expired"]
            )
    if not signal:
        return None
    return {
        "models": models,
        "breaker_timeline": timeline,
        "n_deadline_missed": sum(
            m["deadline_missed"] for m in models.values()
        ),
        "n_swept_expired": sum(m["swept_expired"] for m in models.values()),
        "n_shed": sum(m["shed"] for m in models.values()),
        "n_restarts": sum(m["restarts"] for m in models.values()),
        "n_wedges": sum(m["wedges"] for m in models.values()),
    }


def render_serving_resilience(s: Dict) -> List[str]:
    lines = [
        "serving resilience  deadline-missed %d (swept %d)  shed %d  "
        "restarts %d  wedges %d"
        % (s["n_deadline_missed"], s["n_swept_expired"], s["n_shed"],
           s["n_restarts"], s["n_wedges"])
    ]
    for name, m in sorted(s["models"].items()):
        lines.append(
            "  %s  missed %d  swept %d  shed %d  restarts %d  wedges %d"
            "%s%s"
            % (
                name, m["deadline_missed"], m["swept_expired"], m["shed"],
                m["restarts"], m["wedges"],
                f"  breaker={m['breaker_state']}"
                if m.get("breaker_state") else "",
                "  GAVE-UP (restart budget exhausted)"
                if m.get("gave_up") else "",
            )
        )
    if s["breaker_timeline"]:
        lines.append("  breaker timeline:")
        for ev in s["breaker_timeline"]:
            lines.append(
                "    %s %s%s"
                % (ev["model"], ev["event"],
                   f" ({ev['cause']})" if ev.get("cause") else "")
            )
    return lines


def render_serving(s: Dict) -> List[str]:
    lines = [
        "serving    %d flush(es), %d request(s)"
        % (s["n_flushes"], s["n_requests"])
    ]
    for name, m in sorted(s["models"].items()):
        triggers = " ".join(
            f"{k}={n}" for k, n in sorted(m["by_trigger"].items())
        )
        lat = (
            "p50 %.2fms p99 %.2fms %.1f rps"
            % (m["p50_ms"], m["p99_ms"], m["rps"])
            if m["p50_ms"] is not None and m["p99_ms"] is not None
            and m["rps"] is not None
            else "latency n/a (no completed requests in window)"
        )
        lines.append(
            "  %s v%s%s  req %d in %d flushes  fill %.2f  %s  queue<=%d"
            "%s%s%s"
            % (
                name, m["version"],
                (
                    f" [{m['quantized']}]"
                    if isinstance(m["quantized"], str)
                    else (" [int8]" if m["quantized"] else "")
                ),
                m["requests"], m["flushes"], m["mean_fill"], lat,
                m["queue_depth_max"],
                f"  rejected {m['rejected']}" if m.get("rejected") else "",
                f"  triggers {triggers}" if triggers else "",
                f"  buckets {m['buckets']}" if m["buckets"] else "",
            )
        )
    return lines


def render_health(h: Dict) -> List[str]:
    g = h["last_global"]
    lines = [
        "health     %d record(s), stride %d  |  last: grad-norm %.4g  "
        "weight-norm %.4g  update-ratio %.4g  |  max: grad-norm %s  "
        "update-ratio %s  |  nonfinite steps %d"
        % (
            h["n_records"], h["stride"], g["grad_norm"], g["weight_norm"],
            g["update_ratio"],
            "%.4g" % h["grad_norm_max"] if h["grad_norm_max"] is not None else "n/a",
            "%.4g" % h["update_ratio_max"]
            if h["update_ratio_max"] is not None else "n/a",
            h["nonfinite_steps"],
        )
    ]
    layers = h.get("layers")
    if layers:
        lines.append("  per-layer (last record, by grad norm):")
        width = max(len(p) for p in layers)

        def grad_key(st: Dict) -> float:
            v = float(st["grad_norm"] or 0.0)
            return float("inf") if v != v else v  # NaN (poisoned) sorts first

        rows = sorted(layers.items(), key=lambda kv: -grad_key(kv[1]))
        for path, st in rows:
            flag = ""
            if st.get("nonfinite_grads") or st.get("nonfinite_params"):
                flag = "  NONFINITE(g=%d,w=%d)" % (
                    st.get("nonfinite_grads", 0), st.get("nonfinite_params", 0)
                )
            lines.append(
                "    %-*s  grad %.4g  weight %.4g  upd-ratio %.4g%s"
                % (width, path, st["grad_norm"], st["weight_norm"],
                   st["update_ratio"], flag)
            )
    acts = h.get("acts")
    if acts:
        lines.append("  activations (last record):")
        width = max(len(p) for p in acts)
        for path, st in acts.items():
            lines.append(
                "    %-*s  mean %.4g  std %.4g  zero-frac %.3f"
                % (width, path, st["mean"], st["std"], st["zero_frac"])
            )
    if h["attribution"]:
        lines.append("  non-finite attribution timeline:")
        for a in h["attribution"]:
            lines.append(
                "    iter %s: %s via %s (restored to step %s)"
                % (a["iteration"], a["layer"] or "<global>", a["source"],
                   a["restored_step"])
            )
    return lines


# the serving request's critical-path stage spans, in timeline order
# (serving/batcher emits one of each per sampled/promoted request)
TRACE_STAGES = ("req_queue", "req_assembly", "req_dispatch",
                "req_materialize")


def summarize_trace(span_recs: List[Dict]) -> Dict:
    """Causal-tracing section over the id-bearing ``span`` records.

    The per-stage table aggregates the serving critical path
    (queue → assembly → dispatch → materialize stage spans under each
    ``serve_request`` root) into p50/p99 — the "where does p99 live"
    answer; the slowest-trace exemplar names ONE trace id an operator can
    feed straight to ``/trace?id=`` or ``tools/trace_export.py``.
    ``max_residual_ms`` is the critical-path closure check: for every
    request whose four stage spans are all present, |stages − root| — the
    telescoping contract holds it near zero (docs/observability.md)."""
    roots = [s for s in span_recs if s.get("name") == "serve_request"]
    by_name: Dict[str, List[float]] = {}
    for s in span_recs:
        by_name.setdefault(s["name"], []).append(float(s["dur_s"]))
    stages: Dict[str, Dict] = {}
    for stage in TRACE_STAGES:
        vals = sorted(by_name.get(stage, ()))
        if vals:
            stages[stage] = {
                "n": len(vals),
                "p50_ms": round(percentile(vals, 50) * 1e3, 3),
                "p99_ms": round(percentile(vals, 99) * 1e3, 3),
                "total_s": round(sum(vals), 6),
            }
    out: Dict = {
        "n_spans": len(span_recs),
        "n_traces": len({s["trace_id"] for s in span_recs}),
        "n_requests": len(roots),
        "n_promoted": sum(1 for r in roots if r.get("promoted")),
    }
    if stages:
        out["stages"] = stages
    # stage children parent directly on their request root's span id —
    # grouping on parent_id keeps two requests of one trace apart
    children: Dict[str, List[Dict]] = {}
    for s in span_recs:
        pid = s.get("parent_id")
        if pid is not None and s.get("name") in TRACE_STAGES:
            children.setdefault(pid, []).append(s)
    residuals = []
    for r in roots:
        kids = children.get(r["span_id"], ())
        if len(kids) == len(TRACE_STAGES):
            residuals.append(
                abs(sum(float(k["dur_s"]) for k in kids)
                    - float(r["dur_s"]))
            )
    if residuals:
        out["max_residual_ms"] = round(max(residuals) * 1e3, 3)
    if roots:
        slow = max(roots, key=lambda r: float(r["dur_s"]))
        out["slowest"] = {
            "trace_id": slow["trace_id"],
            "total_ms": round(float(slow["dur_s"]) * 1e3, 3),
            "model": slow.get("model"),
            "promoted": bool(slow.get("promoted")),
            "stages_ms": {
                k["name"]: round(float(k["dur_s"]) * 1e3, 3)
                for k in sorted(children.get(slow["span_id"], ()),
                                key=lambda k: TRACE_STAGES.index(k["name"]))
            },
        }
    return out


def render_trace(t: Dict) -> List[str]:
    lines = [
        "causal traces: %d span(s) in %d trace(s), %d request(s)%s"
        % (t["n_spans"], t["n_traces"], t["n_requests"],
           "  (%d slow-promoted)" % t["n_promoted"]
           if t.get("n_promoted") else "")
    ]
    stages = t.get("stages")
    if stages:
        lines.append("  stage             n     p50_ms     p99_ms    total_s")
        for name in TRACE_STAGES:
            st = stages.get(name)
            if st:
                lines.append(
                    "  %-15s %5d %10.3f %10.3f %10.4f"
                    % (name, st["n"], st["p50_ms"], st["p99_ms"],
                       st["total_s"])
                )
    if t.get("max_residual_ms") is not None:
        lines.append(
            "  critical-path closure: max |stages - total| = %.3fms"
            % t["max_residual_ms"]
        )
    slow = t.get("slowest")
    if slow:
        detail = "  ".join(
            f"{k}={v:.3f}ms" for k, v in slow["stages_ms"].items()
        )
        lines.append(
            "  slowest trace %s  total %.3fms%s%s"
            % (slow["trace_id"], slow["total_ms"],
               f"  model={slow['model']}" if slow.get("model") else "",
               "  PROMOTED" if slow.get("promoted") else "")
        )
        if detail:
            lines.append("    " + detail)
    return lines


def render(summary: Dict) -> str:
    lines = [
        f"records: {summary['n_records']}  steps: {summary['n_steps']}  "
        f"stalls: {summary['n_stalls']}  runs: {summary['n_runs']}"
    ]
    if summary["n_runs"] > 1:
        lines.append(
            "NOTE: stream spans multiple runs — compile counts and "
            "percentiles below are summed across all of them"
        )
    sw = summary.get("step_wall_s")
    if sw:
        lines.append(
            "step wall  p50 %.4fs  p90 %.4fs  p99 %.4fs  mean %.4fs  max %.4fs"
            % (sw["p50"], sw["p90"], sw["p99"], sw["mean"], sw["max"])
        )
    th = summary.get("throughput")
    if th:
        lines.append(
            "throughput mean %.1f rec/s  (first-quarter %.1f -> "
            "last-quarter %.1f, trend x%.3f)"
            % (th["mean"], th["first_quarter_mean"], th["last_quarter_mean"],
               th["trend"])
        )
    hbm = summary.get("hbm_peak_bytes")
    lines.append(
        "HBM peak   %s" % (f"{hbm / 2**20:.1f} MiB" if hbm else "n/a (CPU)")
    )
    gap = summary.get("dispatch_gap")
    if gap:
        lines.append(
            "dispatch gap p50 %.2fms  mean %.2fms  max %.2fms  |  placement "
            "overlapped %.4fs / serialized %.4fs"
            % (gap["p50_s"] * 1e3, gap["mean_s"] * 1e3, gap["max_s"] * 1e3,
               gap["place_overlapped_s"], gap["place_serialized_s"])
        )
    ip = summary.get("input_pipeline")
    if ip:
        depth = ip.get("staging_depth_mean")
        lines.append(
            "input wait p50 %.2fms  mean %.2fms  max %.2fms  |  starved "
            "%.2f%% of step wall%s"
            % (ip["p50_s"] * 1e3, ip["mean_s"] * 1e3, ip["max_s"] * 1e3,
               ip["input_starved_pct"],
               ""
               if depth is None
               else "  |  staging depth mean %.2f" % depth)
        )
    if summary.get("n_warns"):
        reasons = summary.get("warn_reasons") or {}
        detail = " ".join(f"{k}={n}" for k, n in sorted(reasons.items()))
        lines.append(
            "warnings   %d warn record(s)%s"
            % (summary["n_warns"], f"  ({detail})" if detail else "")
        )
        if summary.get("unwarmed_models"):
            lines.append(
                "  UNWARMED models (first request pays the compile): %s"
                % ", ".join(summary["unwarmed_models"])
            )
    comp = summary["compile"]
    lines.append(
        f"compiles   {comp['count']} totaling {comp['seconds']:.2f}s"
        + (
            f"  ({comp['cache_hits']} served from persistent cache)"
            if comp.get("cache_hits") else ""
        )
        + "  "
        + " ".join(
            f"[iter {c['iteration']}: {c['seconds']:.2f}s]"
            for c in comp["timeline"]
        )
    )
    warmup = summary.get("warmup")
    if warmup:
        lines.extend(render_warmup(warmup))
    res = summary.get("resilience") or {}
    if any(
        res.get(k) for k in
        ("n_retries", "n_rollbacks", "n_faults_injected",
         "n_preempt_checkpoints")
    ):
        classes = " ".join(
            f"{cls}={n}" for cls, n in sorted(res["retries_by_class"].items())
        )
        lines.append(
            "resilience retries %d%s  rollbacks %d  faults injected %d  "
            "preempt checkpoints %d"
            % (res["n_retries"], f" ({classes})" if classes else "",
               res["n_rollbacks"], res["n_faults_injected"],
               res["n_preempt_checkpoints"])
        )
    pm = summary.get("postmortem")
    if pm:
        lines.append(
            "postmortem %d bundle(s) sealed  reasons: %s  (max dump "
            "latency %.3fs; last froze %d ring type(s), %d record(s), "
            "%d truncated)"
            % (pm["n_dumps"], ", ".join(pm["reasons"]),
               pm["dump_latency_s_max"], pm["rings_captured"],
               pm["records_captured"], pm["truncated"])
        )
        for b in pm["bundles"]:
            lines.append("  triage: python tools/postmortem.py %s" % b)
    perf = summary.get("perf")
    if perf:
        lines.extend(render_perf(perf))
    health = summary.get("health")
    if health:
        lines.extend(render_health(health))
    serving = summary.get("serving")
    if serving:
        lines.extend(render_serving(serving))
    sres = summary.get("serving_resilience")
    if sres:
        lines.extend(render_serving_resilience(sres))
    tr = summary.get("trace")
    if tr:
        lines.extend(render_trace(tr))
    if summary["spans"]:
        lines.append("span breakdown (host seams):")
        for name, t in summary["spans"].items():
            lines.append(
                f"  {name:20s} {t['s']:9.4f}s  {t['pct']:5.1f}%  n={t['n']}"
            )
    return "\n".join(lines)


# ------------------------------------------------------------------ fleet
def summarize_fleet(streams: Dict[int, List[Dict]]) -> Dict:
    """Merge N per-process streams into one fleet view.

    Alignment is BY (epoch, iteration) — never wall clock, which skews
    across hosts: a step key present on every process is an *aligned* step,
    and its skew is ``max(wall_s) - min(wall_s)`` across the processes that
    completed it. Per-process rows carry the usual single-stream step
    percentiles; the straggler timeline collects the FleetMonitor's
    ``warn reason=straggler/host_lost`` records from every stream (the
    record's ``process_index`` names the FLAGGED process — fleet warns are
    about a subject, not their emitter); per-replica serving health keeps
    the latest serve-record gauges per (process, model); the elastic section
    rebuilds the mesh-size timeline from the driver's
    ``warn reason=mesh_shrunk/mesh_rejoin`` records (membership, fleet
    generation, reshard wall-time, and which checkpoint step the survivors
    assembled from — docs/resilience.md "Elastic fleet")."""
    processes: Dict[int, Dict] = {}
    walls_by_key: Dict[int, Dict[tuple, float]] = {}
    stragglers: List[Dict] = []
    elastic_events: List[Dict] = []
    for k in sorted(streams):
        records = streams[k]
        steps = [r for r in records if r["type"] == "step"]
        host = None
        for r in records:
            if r.get("host") is not None:
                host = r["host"]
                break
        walls = sorted(float(s["wall_s"]) for s in steps if s.get("wall_s"))
        waits = [
            float(s["input_wait_s"]) for s in steps[1:]
            if s.get("input_wait_s") is not None
        ]
        thr = [
            float(s["records_per_sec"]) for s in steps
            if s.get("records_per_sec")
        ]
        entry: Dict = {
            "host": host,
            "n_records": len(records),
            "n_steps": len(steps),
            "last_step": steps[-1]["iteration"] if steps else None,
            "last_epoch": steps[-1].get("epoch") if steps else None,
            "step_wall_s": (
                {
                    "p50": percentile(walls, 50),
                    "mean": round(sum(walls) / len(walls), 6),
                    "max": walls[-1],
                }
                if walls else None
            ),
            "throughput_mean": (
                round(sum(thr) / len(thr), 3) if thr else None
            ),
            "input_wait_mean_s": (
                round(sum(waits) / len(waits), 6) if waits else None
            ),
            "n_warns": sum(1 for r in records if r["type"] == "warn"),
        }
        serving: Dict[str, Dict] = {}
        for r in records:
            if r["type"] != "serve":
                continue
            m = serving.setdefault(r["model"], {})
            m["flushes"] = int(r["iteration"])
            m["queue_depth"] = int(r["queue_depth"])
            for key in ("p50_ms", "p99_ms", "rps", "breaker_state",
                        "deadline_missed", "shed", "version"):
                if r.get(key) is not None:
                    m[key] = r[key]  # latest wins (cumulative/rolling)
        if serving:
            entry["serving"] = serving
        processes[k] = entry
        walls_by_key[k] = {
            (s.get("epoch"), s["iteration"]): float(s["wall_s"])
            for s in steps
            if s.get("wall_s")
        }
        for r in records:
            if r["type"] == "warn" and r.get("reason") in (
                "straggler", "host_lost", "host_left",
            ):
                stragglers.append({
                    "reason": r["reason"],
                    "process_index": r.get("process_index"),
                    "host": r.get("host"),
                    "step": r.get("step"),
                    "median_step": r.get("median_step"),
                    "stale_s": r.get("stale_s"),
                    "ts": r.get("ts"),
                })
            elif r["type"] == "warn" and r.get("reason") in (
                "mesh_shrunk", "mesh_rejoin",
            ):
                elastic_events.append({
                    "reason": r["reason"],
                    "iteration": r.get("iteration"),
                    "members": r.get("members"),
                    "process_count": r.get("process_count"),
                    "processes": r.get("processes"),
                    "generation": r.get("generation"),
                    "restored_step": r.get("restored_step"),
                    "reshard_s": r.get("reshard_s"),
                    "ts": r.get("ts"),
                })
    stragglers.sort(key=lambda s: s.get("ts") or 0.0)
    elastic_events.sort(
        key=lambda e: (e.get("generation") or 0, e.get("ts") or 0.0)
    )

    # aligned-step skew: keys every process completed
    common = None
    for k, by_key in walls_by_key.items():
        keys = set(by_key)
        common = keys if common is None else (common & keys)
    common = common or set()
    skews = sorted(
        max(walls_by_key[k][key] for k in walls_by_key)
        - min(walls_by_key[k][key] for k in walls_by_key)
        for key in common
    )
    out: Dict = {
        "n_processes": len(processes),
        "processes": processes,
        "n_aligned_steps": len(common),
        "skew_s": (
            {
                "p50": round(percentile(skews, 50), 6),
                "p90": round(percentile(skews, 90), 6),
                "max": round(skews[-1], 6),
            }
            if skews else None
        ),
        "stragglers": stragglers,
    }
    if elastic_events:
        reshard_walls = [
            float(e["reshard_s"]) for e in elastic_events
            if e.get("reshard_s") is not None
        ]
        out["elastic"] = {
            "n_shrinks": sum(
                1 for e in elastic_events if e["reason"] == "mesh_shrunk"
            ),
            "n_rejoins": sum(
                1 for e in elastic_events if e["reason"] == "mesh_rejoin"
            ),
            "mesh_timeline": [
                {
                    "iteration": e.get("iteration"),
                    "process_count": e.get("process_count"),
                    "generation": e.get("generation"),
                }
                for e in elastic_events
            ],
            "reshard_s": (
                {
                    "mean": round(
                        sum(reshard_walls) / len(reshard_walls), 6
                    ),
                    "max": round(max(reshard_walls), 6),
                }
                if reshard_walls else None
            ),
            "events": elastic_events,
        }
    last_steps = [
        p["last_step"] for p in processes.values()
        if p["last_step"] is not None
    ]
    if len(last_steps) >= 2:
        med = statistics.median(last_steps)
        out["step_lag"] = {
            "median_last_step": med,
            "behind": {
                k: med - p["last_step"]
                for k, p in processes.items()
                if p["last_step"] is not None and p["last_step"] < med
            },
        }
    return out


def load_fleet(path: str) -> Dict[int, List[Dict]]:
    return {k: load(p) for k, p in fleet_streams(path).items()}


def render_fleet(f: Dict) -> str:
    lines = [
        "fleet      %d process(es), %d aligned step(s) (merged by "
        "(epoch, iteration))"
        % (f["n_processes"], f["n_aligned_steps"])
    ]
    for k, p in sorted(f["processes"].items()):
        sw = p["step_wall_s"]
        lines.append(
            "  p%-3s %-12s steps %-4d (last e%s i%s)  %s  thr %s  "
            "input-wait %s%s"
            % (
                k, p["host"] or "?", p["n_steps"],
                p["last_epoch"] if p["last_epoch"] is not None else "-",
                p["last_step"] if p["last_step"] is not None else "-",
                "wall p50 %.4fs max %.4fs" % (sw["p50"], sw["max"])
                if sw else "wall n/a",
                "%.1f rec/s" % p["throughput_mean"]
                if p["throughput_mean"] is not None else "n/a",
                "%.2fms" % (p["input_wait_mean_s"] * 1e3)
                if p["input_wait_mean_s"] is not None else "n/a",
                f"  warns {p['n_warns']}" if p["n_warns"] else "",
            )
        )
    skew = f.get("skew_s")
    if skew:
        lines.append(
            "  aligned-step skew p50 %.2fms  p90 %.2fms  max %.2fms"
            % (skew["p50"] * 1e3, skew["p90"] * 1e3, skew["max"] * 1e3)
        )
    lag = f.get("step_lag")
    if lag and lag["behind"]:
        lines.append(
            "  step-count lag vs fleet median (%s): %s"
            % (
                lag["median_last_step"],
                "  ".join(
                    f"p{k} behind {int(n)}"
                    for k, n in sorted(lag["behind"].items())
                ),
            )
        )
    if f["stragglers"]:
        lines.append("  straggler timeline:")
        for s in f["stragglers"]:
            if s["reason"] == "straggler":
                detail = "step %s vs fleet median %s" % (
                    s.get("step"), s.get("median_step"),
                )
            elif s["reason"] == "host_left":
                detail = "clean shutdown at step %s" % (s.get("step"),)
            else:
                detail = "heartbeat stale %ss" % (s.get("stale_s"),)
            lines.append(
                "    p%s %s (%s)%s"
                % (s["process_index"], s["reason"], detail,
                   f"  [host {s['host']}]" if s.get("host") else "")
            )
    el = f.get("elastic")
    if el:
        rs = el.get("reshard_s")
        lines.append(
            "  elastic fleet: %d shrink(s), %d rejoin(s)%s"
            % (
                el["n_shrinks"], el["n_rejoins"],
                "  reshard wall mean %.2fms max %.2fms"
                % (rs["mean"] * 1e3, rs["max"] * 1e3) if rs else "",
            )
        )
        for e in el["events"]:
            lines.append(
                "    i%s %s %s -> %s active process(es)  gen %s  "
                "assembled from checkpoint step %s%s"
                % (
                    e.get("iteration"),
                    "shrink" if e["reason"] == "mesh_shrunk" else "rejoin",
                    e.get("members"),
                    e.get("process_count"),
                    e.get("generation"),
                    e.get("restored_step"),
                    "  (%.2fms)" % (e["reshard_s"] * 1e3)
                    if e.get("reshard_s") is not None else "",
                )
            )
    served = {
        (k, m): st
        for k, p in f["processes"].items()
        for m, st in (p.get("serving") or {}).items()
    }
    if served:
        lines.append("  per-replica serving health:")
        for (k, m), st in sorted(served.items()):
            lines.append(
                "    p%s %s v%s  queue %s  p99 %s  breaker=%s  missed %s"
                % (
                    k, m, st.get("version", "?"), st.get("queue_depth"),
                    "%.2fms" % st["p99_ms"] if st.get("p99_ms") is not None
                    else "n/a",
                    st.get("breaker_state") or "n/a",
                    st.get("deadline_missed", 0),
                )
            )
    return "\n".join(lines)


# ---------------------------------------------------------------- selftest
def selftest() -> int:
    """CI gate: summarize the checked-in golden fixtures (single-stream AND
    the 3-process fleet dir) and assert the numbers — a schema or summarizer
    drift fails fast, with no jax needed."""
    fixtures_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        os.pardir, "tests", "fixtures",
    )
    fixture = os.path.join(fixtures_dir, "obs_golden.jsonl")
    records = load(fixture)
    s = summarize(records)
    fleet = summarize_fleet(load_fleet(os.path.join(fixtures_dir,
                                                    "fleet_golden")))
    expect = [
        # fleet merge (3 simulated per-process streams; p2 is the injected
        # straggler: 4 slow steps, named in the timeline)
        ("fleet.n_processes", fleet["n_processes"], 3),
        ("fleet.n_aligned_steps", fleet["n_aligned_steps"], 4),
        ("fleet.skew_s.max", fleet["skew_s"]["max"], 0.2),
        ("fleet.skew_s.p50", fleet["skew_s"]["p50"], 0.2),
        ("fleet.p0.n_steps", fleet["processes"][0]["n_steps"], 8),
        ("fleet.p0.step_wall_p50",
         fleet["processes"][0]["step_wall_s"]["p50"], 0.1),
        ("fleet.p2.n_steps", fleet["processes"][2]["n_steps"], 4),
        ("fleet.p2.host", fleet["processes"][2]["host"], "h2"),
        ("fleet.step_lag.behind", fleet["step_lag"]["behind"], {2: 4}),
        ("fleet.straggler named",
         [(e["reason"], e["process_index"], e["median_step"])
          for e in fleet["stragglers"]],
         [("straggler", 2, 8), ("host_left", 1, None)]),
        # elastic section (docs/resilience.md "Elastic fleet"): mesh-size
        # timeline from the mesh_shrunk/mesh_rejoin warns + reshard wall
        ("fleet.elastic.n_shrinks", fleet["elastic"]["n_shrinks"], 1),
        ("fleet.elastic.n_rejoins", fleet["elastic"]["n_rejoins"], 1),
        ("fleet.elastic.mesh_timeline", fleet["elastic"]["mesh_timeline"],
         [{"iteration": 6, "process_count": 2, "generation": 1},
          {"iteration": 8, "process_count": 3, "generation": 2}]),
        ("fleet.elastic.reshard_s.max",
         fleet["elastic"]["reshard_s"]["max"], 0.045),
        ("fleet.elastic.assembled-from",
         [e["restored_step"] for e in fleet["elastic"]["events"]], [6, 8]),
        ("fleet.p1.serving.m1.queue_depth",
         fleet["processes"][1]["serving"]["m1"]["queue_depth"], 1),
        ("fleet.p1.serving.m1.p99_ms",
         fleet["processes"][1]["serving"]["m1"]["p99_ms"], 7.5),
        ("fleet.p1.serving.m1.breaker",
         fleet["processes"][1]["serving"]["m1"]["breaker_state"], "closed"),
        ("n_steps", s["n_steps"], 8),
        ("n_stalls", s["n_stalls"], 1),
        ("compile.count", s["compile"]["count"], 1),
        ("compile.seconds", s["compile"]["seconds"], 2.5),
        ("step p50", s["step_wall_s"]["p50"], 0.1),
        ("step p90", s["step_wall_s"]["p90"], 0.3),
        ("step p99", s["step_wall_s"]["p99"], 0.3),
        ("hbm_peak_bytes", s["hbm_peak_bytes"], 12345678),
        ("throughput.trend", s["throughput"]["trend"], 0.4667),
        ("spans.prefetch.n", s["spans"]["prefetch"]["n"], 8),
        ("spans.dispatch.s", s["spans"]["dispatch"]["s"], 0.21),
        ("resilience.n_retries", s["resilience"]["n_retries"], 1),
        ("resilience.retries_by_class",
         s["resilience"]["retries_by_class"], {"transient": 1}),
        ("resilience.n_rollbacks", s["resilience"]["n_rollbacks"], 1),
        ("resilience.n_faults_injected",
         s["resilience"]["n_faults_injected"], 1),
        ("resilience.n_preempt_checkpoints",
         s["resilience"]["n_preempt_checkpoints"], 1),
        ("health.n_records", s["health"]["n_records"], 4),
        ("health.stride", s["health"]["stride"], 2),
        ("health.nonfinite_steps", s["health"]["nonfinite_steps"], 1),
        ("health.grad_norm_max", s["health"]["grad_norm_max"], 1.0),
        ("health.layers nonfinite",
         s["health"]["layers"]["Linear_0/weight"]["nonfinite_grads"], 384),
        ("health.attribution", s["health"]["attribution"],
         [{"iteration": 8, "layer": "Linear_0/weight", "source": "grads",
           "restored_step": 6}]),
        ("n_warns", s["n_warns"], 8),
        ("warn_reasons", s["warn_reasons"],
         {"update_ratio": 1, "activation_drift": 1, "unwarmed_model": 1,
          "deadline_exceeded": 1, "circuit_open": 1, "circuit_closed": 1,
          "worker_restart": 1, "perf_regression": 1}),
        # perf-accounting section (obs/perf.py): MFU series + decomposition
        ("perf.n_records", s["perf"]["n_records"], 2),
        ("perf.mfu_mean", s["perf"]["mfu_mean"], 0.225),
        ("perf.last.mfu", s["perf"]["last"]["mfu"], 0.2),
        ("perf.bound", s["perf"]["bound"], "compute"),
        ("perf.model_flops", s["perf"]["model_flops"], 3000000000.0),
        ("perf.breakdown_mean.compute",
         s["perf"]["breakdown_mean"]["compute_s"], 0.085),
        ("perf.breakdown_mean.input",
         s["perf"]["breakdown_mean"]["input_s"], 0.031),
        ("unwarmed_models", s["unwarmed_models"], ["m3"]),
        ("compile.cache_hits", s["compile"]["cache_hits"], 0),
        ("warmup.boot_to_ready_s", s["warmup"]["boot_to_ready_s"], 1.3),
        ("warmup.total_fresh_compiles",
         s["warmup"]["total_fresh_compiles"], 8),
        ("warmup.all_cache_hits", s["warmup"]["all_cache_hits"], False),
        ("warmup.m2.warm_start",
         s["warmup"]["models"]["m2"]["warm_start"], True),
        ("warmup.m2.fresh_compiles",
         s["warmup"]["models"]["m2"]["fresh_compiles"], 0),
        ("warmup.m1.buckets", s["warmup"]["models"]["m1"]["buckets"],
         [8, 16]),
        # the hot-swap warmup must NOT shadow the boot's numbers
        ("warmup.m1.seconds (boot, not swap)",
         s["warmup"]["models"]["m1"]["seconds"], 1.25),
        ("warmup.m1.swap_warmups",
         s["warmup"]["models"]["m1"]["swap_warmups"], 1),
        ("serving.n_flushes", s["serving"]["n_flushes"], 5),
        ("serving.n_requests", s["serving"]["n_requests"], 29),
        ("serving.m1.mean_fill", s["serving"]["models"]["m1"]["mean_fill"],
         0.75),
        ("serving.m1.by_trigger", s["serving"]["models"]["m1"]["by_trigger"],
         {"max_batch": 2, "max_delay": 2}),
        ("serving.m1.p50_ms", s["serving"]["models"]["m1"]["p50_ms"], 2.5),
        ("serving.m1.p99_ms", s["serving"]["models"]["m1"]["p99_ms"], 7.5),
        ("serving.m1.version", s["serving"]["models"]["m1"]["version"], 2),
        ("serving.m1.buckets", s["serving"]["models"]["m1"]["buckets"],
         [8, 16]),
        ("serving.m2.quantized", s["serving"]["models"]["m2"]["quantized"],
         True),
        ("serving.m2.rps", s["serving"]["models"]["m2"]["rps"], 55.5),
        ("serving.m2.rejected", s["serving"]["models"]["m2"]["rejected"], 2),
        ("serving.m1.rejected", s["serving"]["models"]["m1"]["rejected"], 0),
        # causal-tracing section (id-bearing span records): 2 request
        # chains (one sampled, one slow-promoted) + a linking serve_flush
        ("serving.m1.trace_id", s["serving"]["models"]["m1"]["trace_id"],
         "aaaa0001-00000010"),
        ("trace.n_spans", s["trace"]["n_spans"], 11),
        ("trace.n_traces", s["trace"]["n_traces"], 3),
        ("trace.n_requests", s["trace"]["n_requests"], 2),
        ("trace.n_promoted", s["trace"]["n_promoted"], 1),
        ("trace.max_residual_ms", s["trace"]["max_residual_ms"], 0.0),
        ("trace.req_queue.p50_ms",
         s["trace"]["stages"]["req_queue"]["p50_ms"], 1.0),
        ("trace.req_queue.p99_ms",
         s["trace"]["stages"]["req_queue"]["p99_ms"], 30.0),
        ("trace.req_dispatch.p50_ms",
         s["trace"]["stages"]["req_dispatch"]["p50_ms"], 2.0),
        ("trace.req_dispatch.n",
         s["trace"]["stages"]["req_dispatch"]["n"], 2),
        ("trace.slowest.trace_id",
         s["trace"]["slowest"]["trace_id"], "aaaa0001-00000010"),
        ("trace.slowest.total_ms", s["trace"]["slowest"]["total_ms"], 40.0),
        ("trace.slowest.promoted", s["trace"]["slowest"]["promoted"], True),
        ("trace.slowest.stages_ms",
         s["trace"]["slowest"]["stages_ms"],
         {"req_queue": 30.0, "req_assembly": 1.0, "req_dispatch": 8.0,
          "req_materialize": 1.0}),
        ("input_pipeline.p50_s", s["input_pipeline"]["p50_s"], 0.01),
        ("input_pipeline.mean_s", s["input_pipeline"]["mean_s"], 0.015714),
        ("input_pipeline.max_s", s["input_pipeline"]["max_s"], 0.03),
        ("input_pipeline.input_starved_pct",
         s["input_pipeline"]["input_starved_pct"], 11.96),
        ("input_pipeline.staging_depth_mean",
         s["input_pipeline"]["staging_depth_mean"], 1.43),
        ("dispatch_gap.p50_s", s["dispatch_gap"]["p50_s"], 0.02),
        ("dispatch_gap.mean_s", s["dispatch_gap"]["mean_s"], 0.02625),
        ("dispatch_gap.max_s", s["dispatch_gap"]["max_s"], 0.07),
        ("dispatch_gap.place_overlapped_s",
         s["dispatch_gap"]["place_overlapped_s"], 0.03),
        ("dispatch_gap.place_serialized_s",
         s["dispatch_gap"]["place_serialized_s"], 0.05),
        # serving-resilience section (deadlines / breaker / supervisor)
        ("serving_resilience.n_deadline_missed",
         s["serving_resilience"]["n_deadline_missed"], 3),
        ("serving_resilience.n_swept_expired",
         s["serving_resilience"]["n_swept_expired"], 2),
        ("serving_resilience.n_shed",
         s["serving_resilience"]["n_shed"], 1),
        ("serving_resilience.n_restarts",
         s["serving_resilience"]["n_restarts"], 1),
        ("serving_resilience.m1.deadline_missed",
         s["serving_resilience"]["models"]["m1"]["deadline_missed"], 3),
        ("serving_resilience.m1.breaker_state",
         s["serving_resilience"]["models"]["m1"]["breaker_state"], "closed"),
        ("serving_resilience.m2.restarts",
         s["serving_resilience"]["models"]["m2"]["restarts"], 1),
        ("serving_resilience.breaker_timeline",
         [(e["model"], e["event"])
          for e in s["serving_resilience"]["breaker_timeline"]],
         [("m2", "circuit_open"), ("m2", "circuit_closed")]),
        # flight-recorder section (obs/blackbox.py): the sealed-bundle
        # record an abnormal exit leaves as the stream's last word
        ("postmortem.n_dumps", s["postmortem"]["n_dumps"], 1),
        ("postmortem.reasons", s["postmortem"]["reasons"],
         ["optimize_FaultInjected"]),
        ("postmortem.bundles", s["postmortem"]["bundles"],
         ["/run/postmortem/000-optimize_FaultInjected"]),
        ("postmortem.dump_latency_s_max",
         s["postmortem"]["dump_latency_s_max"], 0.012),
        ("postmortem.rings_captured", s["postmortem"]["rings_captured"], 5),
        ("postmortem.records_captured",
         s["postmortem"]["records_captured"], 97),
        ("postmortem.truncated", s["postmortem"]["truncated"], 3),
    ]
    failed = [
        f"{name}: expected {want!r}, got {got!r}"
        for name, got, want in expect
        if got != want
    ]
    if failed:
        print("obs_report selftest FAILED:", file=sys.stderr)
        for f in failed:
            print("  " + f, file=sys.stderr)
        return 1
    # renderers must not crash on the golden summaries either
    render(s)
    render_fleet(fleet)
    print(
        f"obs_report selftest OK ({len(records)} golden records, "
        f"{fleet['n_processes']}-process fleet fixture)"
    )
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("jsonl", nargs="?",
                    help="telemetry p<k>.jsonl (or a run dir holding one)")
    ap.add_argument("--fleet", metavar="RUN_DIR",
                    help="merge every per-process stream (telemetry/"
                         "p*.jsonl; events.jsonl read-compat) of a shared "
                         "run dir by (epoch, iteration)")
    ap.add_argument("--json", action="store_true", help="emit JSON summary")
    ap.add_argument("--selftest", action="store_true",
                    help="validate + summarize the golden fixtures (CI gate)")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if args.fleet:
        streams = load_fleet(args.fleet)
        fsum = summarize_fleet(streams)
        if args.json:
            print(json.dumps(fsum, indent=1))
        else:
            print(render_fleet(fsum))
            for k in sorted(streams):
                print(f"\n--- p{k} ---")
                print(render(summarize(streams[k])))
        return 0
    if not args.jsonl:
        ap.error("need a telemetry JSONL path (or --fleet / --selftest)")
    summary = summarize(load(resolve_stream(args.jsonl)))
    print(json.dumps(summary, indent=1) if args.json else render(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
