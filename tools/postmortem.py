#!/usr/bin/env python3
"""Render a postmortem bundle (obs/blackbox.py) into a triage report.

Standalone and stdlib-only by design — triage happens on whatever machine
the artifacts were scped to, which has no jax and no bigdl_tpu. The bundle
format is the verified layout ``dump_postmortem`` writes: payload files
first, ``MANIFEST.json`` (sha256 + bytes per file) sealed LAST, so this
tool can refuse a half-written or corrupted bundle instead of mis-triaging
it.

Usage:
    python tools/postmortem.py <bundle-dir>          # one bundle
    python tools/postmortem.py --fleet <run-dir>     # merge every bundle
                                                     # under <run-dir>/postmortem
                                                     # by fleet identity
    python tools/postmortem.py --selftest            # golden-fixture gate

The report answers the four triage questions in order: what died (reason +
error), where it was (last-known-good step), why (failing seam + stack ×
span correlation), and how it was doing (checkpoint pointer, fleet
heartbeats). ``--fleet`` additionally cross-references survivors' bundles
against the LOST hosts' last heartbeats — the host that died hardest is
exactly the one with no bundle of its own. Documented in
docs/observability.md "Flight recorder & postmortems".
"""

import argparse
import hashlib
import json
import os
import shutil
import sys
import tempfile

MANIFEST_NAME = "MANIFEST.json"
BUNDLE_FORMAT = "bigdl-postmortem-v1"
HARD_CRASH_DIRNAME = "hard_crash"

#: record types whose LAST occurrence names the failing seam, in priority
#: order (a deliberate chaos injection beats a generic warn)
_SEAM_TYPES = ("fault_injected", "stall", "preempt_checkpoint",
               "retry", "rollback", "warn")


class BundleError(RuntimeError):
    pass


class BundleTruncated(BundleError):
    pass


class BundleTampered(BundleError):
    pass


# --------------------------------------------------------------------------
# verify + load (stdlib mirror of blackbox.verify_bundle/load_bundle)
# --------------------------------------------------------------------------

def _file_digest(path):
    h = hashlib.sha256()
    size = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1024 * 1024)
            if not chunk:
                break
            h.update(chunk)
            size += len(chunk)
    return h.hexdigest(), size


def verify_bundle(path):
    mpath = os.path.join(path, MANIFEST_NAME)
    if not os.path.exists(mpath):
        raise BundleTruncated(
            "%s: %s is missing (writer died before sealing?)"
            % (path, MANIFEST_NAME))
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise BundleTruncated("%s: unreadable manifest (%s)" % (path, e))
    if manifest.get("format") != BUNDLE_FORMAT:
        raise BundleTampered("%s: format %r is not %r"
                             % (path, manifest.get("format"), BUNDLE_FORMAT))
    for rel, meta in sorted((manifest.get("files") or {}).items()):
        fp = os.path.join(path, rel)
        if not os.path.exists(fp):
            raise BundleTruncated("%s: %s is missing" % (path, rel))
        digest, size = _file_digest(fp)
        if size != meta.get("bytes"):
            raise BundleTruncated(
                "%s: %s is %d bytes, manifest says %s (truncated?)"
                % (path, rel, size, meta.get("bytes")))
        if digest != meta.get("sha256"):
            raise BundleTampered(
                "%s: %s content checksum mismatch" % (path, rel))
    return manifest


def load_bundle(path):
    manifest = verify_bundle(path)
    out = {"path": os.path.abspath(path), "manifest": manifest, "rings": {}}
    for rel in manifest.get("files") or {}:
        if rel.startswith("rings" + os.sep) and rel.endswith(".jsonl"):
            rtype = os.path.basename(rel)[:-len(".jsonl")]
            with open(os.path.join(path, rel)) as f:
                out["rings"][rtype] = [
                    json.loads(line) for line in f if line.strip()]
    for name in ("reason", "fingerprint", "trace", "fleet", "checkpoint"):
        fp = os.path.join(path, name + ".json")
        out[name] = None
        if os.path.exists(fp):
            with open(fp) as f:
                out[name] = json.load(f)
    stacks = os.path.join(path, "stacks.txt")
    out["stacks"] = None
    if os.path.exists(stacks):
        with open(stacks) as f:
            out["stacks"] = f.read()
    return out


# --------------------------------------------------------------------------
# triage
# --------------------------------------------------------------------------

def last_known_good(bundle):
    """The newest step record in the rings — the last step the run is KNOWN
    to have completed (its record only exists because the step finished)."""
    steps = bundle["rings"].get("step") or []
    return steps[-1] if steps else None


def failing_seam(bundle):
    """The newest seam-naming record across the failure-shaped ring types
    (priority: a chaos ``fault_injected`` beats a generic ``warn``)."""
    best, best_rank = None, None
    for rank, rtype in enumerate(_SEAM_TYPES):
        recs = bundle["rings"].get(rtype) or []
        if not recs:
            continue
        cand = recs[-1]
        ts = cand.get("ts") or 0
        if best is None or rank < best_rank or (
                rank == best_rank and ts > (best.get("ts") or 0)):
            if best is None or rank < best_rank:
                best, best_rank = cand, rank
    return best


def critical_path(bundle):
    """Walk the active TraceContext's parent chain through the dumped span
    ring: deepest (active) span first, root last."""
    trace = bundle.get("trace") or {}
    ctx = trace.get("context")
    spans = trace.get("spans") or []
    if not ctx:
        return []
    by_id = {}
    for s in spans:
        sid = s.get("span_id")
        if sid:
            by_id.setdefault(sid, s)
    chain, seen = [], set()
    cursor = ctx.get("span_id")
    # the active context itself may have no emitted span record yet (it is
    # the one that was in flight) — represent it structurally
    if cursor not in by_id:
        chain.append({"span_id": cursor, "name": "<in flight>",
                      "parent_id": ctx.get("parent_id")})
        cursor = ctx.get("parent_id")
    while cursor and cursor not in seen:
        seen.add(cursor)
        s = by_id.get(cursor)
        if s is None:
            break
        chain.append(s)
        cursor = s.get("parent_id")
    return chain


def stack_span_correlation(bundle):
    """Which dumped thread stacks belong to threads that also emitted spans
    in the active trace — the 'who was doing the dying work' join."""
    trace = bundle.get("trace") or {}
    span_threads = {s.get("thread") for s in (trace.get("spans") or [])
                    if s.get("thread")}
    stacks = bundle.get("stacks") or ""
    stack_threads = set()
    for line in stacks.splitlines():
        if line.startswith("Thread ") and " (ident " in line:
            stack_threads.add(line[len("Thread "):].split(" (ident ")[0])
    return sorted(span_threads & stack_threads)


def render(bundle):
    """One bundle -> triage report text."""
    lines = []
    reason = bundle.get("reason") or {}
    fp = bundle.get("fingerprint") or {}
    ident = fp.get("identity") or {}
    lines.append("== postmortem triage: %s ==" % bundle["path"])
    lines.append("reason: %s" % reason.get("reason", "<unknown>"))
    err = reason.get("error")
    if err:
        lines.append("error: %s" % err.get("repr", err.get("class")))
    lines.append(
        "process: p%s/%s host=%s pid=%s"
        % (ident.get("process_index", "?"), ident.get("process_count", "?"),
           ident.get("host", "?"), fp.get("pid", "?")))
    counts = reason.get("rings") or {}
    kept = sum(c.get("kept", 0) for c in counts.values())
    truncated = sum(max(0, c.get("seen", 0) - c.get("kept", 0))
                    for c in counts.values())
    lines.append(
        "rings: %d types, %d records kept, %d truncated; dump took %ss"
        % (len(counts), kept, truncated, reason.get("dump_latency_s", "?")))

    lkg = last_known_good(bundle)
    if lkg is not None:
        lines.append(
            "last known good: step %s (epoch %s) loss=%s wall_s=%s"
            % (lkg.get("iteration"), lkg.get("epoch"),
               lkg.get("loss"), lkg.get("wall_s")))
    else:
        lines.append("last known good: <no completed step in the rings>")

    seam = failing_seam(bundle)
    if seam is not None:
        detail = {k: v for k, v in seam.items()
                  if k not in ("ts", "process_index", "process_count",
                               "host", "type")}
        lines.append("failing seam: %s %s" % (seam.get("type"), detail))
    else:
        lines.append("failing seam: <none recorded>")

    chain = critical_path(bundle)
    if chain:
        lines.append("critical path (active -> root): "
                     + " <- ".join(s.get("name", "?") for s in chain))
    correlated = stack_span_correlation(bundle)
    if correlated:
        lines.append("stack x span: threads %s appear in BOTH the dumped "
                     "stacks and the active trace's spans"
                     % ", ".join(correlated))

    ckpt = bundle.get("checkpoint")
    if ckpt:
        verdict = ckpt.get("verify")
        lines.append(
            "checkpoint: step %s at %s (%s)"
            % (ckpt.get("step"), ckpt.get("directory"),
               "verified OK" if verdict is None else "BAD: %s" % verdict))
    fleet = bundle.get("fleet") or {}
    if fleet:
        beats = []
        for k in sorted(fleet, key=lambda s: int(s)):
            hb = fleet[k]
            beats.append("p%s@step %s%s" % (
                k, hb.get("step"),
                " (leaving)" if hb.get("leaving") else ""))
        lines.append("fleet heartbeats: " + "  ".join(beats))
    return "\n".join(lines)


# --------------------------------------------------------------------------
# fleet merge
# --------------------------------------------------------------------------

def find_bundles(run_dir):
    """Every sealed bundle under ``<run_dir>/postmortem`` (and the run dir
    itself when pointed straight at a ``postmortem/`` directory)."""
    roots = [os.path.join(run_dir, "postmortem"), run_dir]
    out = []
    for root in roots:
        if not os.path.isdir(root):
            continue
        for name in sorted(os.listdir(root)):
            d = os.path.join(root, name)
            if (os.path.isdir(d)
                    and os.path.exists(os.path.join(d, MANIFEST_NAME))):
                out.append(d)
        if out:
            break
    return out


def hard_crash_artifact(run_dir):
    """The faulthandler artifact, if a hard crash left one: the pre-opened
    ``postmortem/hard_crash/stacks.txt`` is only non-empty when a fatal
    signal fired (there is no manifest — Python was gone)."""
    for root in (os.path.join(run_dir, "postmortem"), run_dir):
        stacks = os.path.join(root, HARD_CRASH_DIRNAME, "stacks.txt")
        try:
            if os.path.getsize(stacks) > 0:
                return os.path.dirname(stacks)
        except OSError:
            continue
    return None


def merge_fleet(run_dir):
    """Load every bundle in the run dir, grouped by fleet identity, plus
    the lost-host cross-reference: processes that appear in survivors'
    heartbeat snapshots but left no bundle of their own."""
    bundles = [load_bundle(p) for p in find_bundles(run_dir)]
    by_proc = {}
    traces = set()
    for b in bundles:
        ident = (b.get("fingerprint") or {}).get("identity") or {}
        by_proc.setdefault(int(ident.get("process_index", 0)), []).append(b)
        ctx = (b.get("trace") or {}).get("context")
        if ctx and ctx.get("trace_id"):
            traces.add(ctx["trace_id"])
    # lost hosts: seen in ANY survivor's heartbeat snapshot, no own bundle
    lost = {}
    for b in bundles:
        for k, hb in (b.get("fleet") or {}).items():
            k = int(k)
            if k in by_proc:
                continue
            cur = lost.get(k)
            if cur is None or (hb.get("ts") or 0) > (cur.get("ts") or 0):
                lost[k] = hb
    return {"run_dir": os.path.abspath(run_dir), "bundles": bundles,
            "by_process": by_proc, "traces": sorted(traces), "lost": lost,
            "hard_crash": hard_crash_artifact(run_dir)}


def render_fleet(merged):
    lines = ["== fleet postmortem: %s ==" % merged["run_dir"],
             "%d bundle(s) from %d process(es); %d shared trace(s)"
             % (len(merged["bundles"]), len(merged["by_process"]),
                len(merged["traces"]))]
    for k in sorted(merged["by_process"]):
        for b in merged["by_process"][k]:
            reason = (b.get("reason") or {}).get("reason", "<unknown>")
            lkg = last_known_good(b)
            lines.append(
                "  p%d: %s (last good step %s) — %s"
                % (k, reason,
                   lkg.get("iteration") if lkg else "none", b["path"]))
    for k in sorted(merged["lost"]):
        hb = merged["lost"][k]
        lines.append(
            "  p%d: LOST — no bundle; last heartbeat step %s ts %s%s "
            "(cross-referenced from survivors' fleet snapshots)"
            % (k, hb.get("step"), hb.get("ts"),
               " leaving" if hb.get("leaving") else ""))
    if merged["hard_crash"]:
        lines.append("  hard crash artifact: %s (faulthandler stacks — "
                     "no manifest, Python died mid-flight)"
                     % merged["hard_crash"])
    for b in merged["bundles"]:
        lines.append("")
        lines.append(render(b))
    return "\n".join(lines)


# --------------------------------------------------------------------------
# selftest
# --------------------------------------------------------------------------

def _golden_dir():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "tests", "fixtures", "postmortem_golden")


def selftest():
    """Gate against the committed golden bundle: verify-on-load accepts it,
    the triage report extracts the planted facts, and tampered/truncated
    copies are rejected TYPED."""
    golden = os.path.normpath(_golden_dir())
    bundle_dirs = find_bundles(golden)
    expect = []
    if not bundle_dirs:
        print("postmortem selftest: FAIL — no golden bundle under %s"
              % golden)
        return 1
    b = load_bundle(bundle_dirs[0])
    reason = (b.get("reason") or {}).get("reason")
    expect.append(("golden reason", reason, "golden_probe"))
    lkg = last_known_good(b)
    expect.append(("golden last-good step",
                   lkg and lkg.get("iteration"), 7))
    seam = failing_seam(b)
    expect.append(("golden failing seam type",
                   seam and seam.get("type"), "fault_injected"))
    expect.append(("golden failing seam name",
                   seam and seam.get("seam"), "dispatch"))
    report = render(b)
    expect.append(("render names reason",
                   "golden_probe" in report, True))
    expect.append(("render names last-good step",
                   "last known good: step 7" in report, True))
    expect.append(("render names the seam",
                   "fault_injected" in report, True))
    chain = critical_path(b)
    expect.append(("critical path reaches the root",
                   bool(chain) and chain[-1].get("parent_id") is None, True))
    fleet = merge_fleet(golden)
    expect.append(("fleet merge sees the bundle",
                   len(fleet["bundles"]), 1))
    expect.append(("fleet merge cross-references the lost host",
                   sorted(fleet["lost"]), [1]))
    freport = render_fleet(fleet)
    expect.append(("fleet render flags the lost host",
                   "p1: LOST" in freport, True))

    # tamper/truncate rejection, on throwaway copies
    tmp = tempfile.mkdtemp(prefix="postmortem_selftest_")
    try:
        tampered = os.path.join(tmp, "tampered")
        shutil.copytree(bundle_dirs[0], tampered)
        with open(os.path.join(tampered, "reason.json"), "a") as f:
            f.write(" ")
        try:
            verify_bundle(tampered)
            got = "no error"
        except BundleTruncated:
            got = "truncated"  # size changed -> truncation surfaces first
        except BundleTampered:
            got = "tampered"
        expect.append(("appended byte -> typed rejection",
                       got in ("truncated", "tampered"), True))

        flipped = os.path.join(tmp, "flipped")
        shutil.copytree(bundle_dirs[0], flipped)
        rp = os.path.join(flipped, "reason.json")
        with open(rp) as f:
            body = f.read()
        with open(rp, "w") as f:
            f.write(body.replace("golden_probe", "golden_frobe"))
        try:
            verify_bundle(flipped)
            got = "no error"
        except BundleTampered:
            got = "tampered"
        except BundleTruncated:
            got = "truncated"
        expect.append(("same-size content flip -> BundleTampered",
                       got, "tampered"))

        truncated = os.path.join(tmp, "truncated")
        shutil.copytree(bundle_dirs[0], truncated)
        os.remove(os.path.join(truncated, "stacks.txt"))
        try:
            verify_bundle(truncated)
            got = "no error"
        except BundleTruncated:
            got = "truncated"
        except BundleTampered:
            got = "tampered"
        expect.append(("missing file -> BundleTruncated", got, "truncated"))

        sealless = os.path.join(tmp, "sealless")
        shutil.copytree(bundle_dirs[0], sealless)
        os.remove(os.path.join(sealless, MANIFEST_NAME))
        try:
            verify_bundle(sealless)
            got = "no error"
        except BundleTruncated:
            got = "truncated"
        expect.append(("missing manifest -> BundleTruncated",
                       got, "truncated"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    failures = [(name, got, want) for name, got, want in expect
                if got != want]
    for name, got, want in failures:
        print("postmortem selftest: FAIL %s: got %r want %r"
              % (name, got, want))
    if failures:
        return 1
    print("postmortem selftest: OK (%d checks)" % len(expect))
    return 0


# --------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", nargs="?",
                    help="bundle dir (or run dir with --fleet)")
    ap.add_argument("--fleet", action="store_true",
                    help="merge every bundle under <path>/postmortem")
    ap.add_argument("--selftest", action="store_true")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if not args.path:
        ap.error("a bundle dir is required (or --selftest)")
    try:
        if args.fleet:
            print(render_fleet(merge_fleet(args.path)))
        else:
            print(render(load_bundle(args.path)))
    except BundleError as e:
        print("REJECTED: %s: %s" % (type(e).__name__, e))
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
