"""AOT executable artifacts: serialize once, boot a replica in seconds.

A fresh ``ModelServer`` replica (or a preempted trainer restored onto a new
host) pays full per-(model, bucket) warmup compiles unless the persistent
XLA compile cache happens to already be local — the biggest latency cliff
between "process up" and "serving traffic". This module is the TPU-native
analogue of BigDL shipping the model + its execution plan to every Spark
executor at task start (arXiv 1804.05839): an **artifact bundle** captures
everything a replica needs to reach ready WITHOUT tracing or compiling from
scratch.

Bundle layout (a directory)::

    <bundle>/
      modules/<name>.jexp   jax.export-serialized lowered StableHLO modules
                            (one per (model, version, bucket) for serving;
                            the cached train step for trainers)
      cache/<entries>       persistent-compile-cache entries harvested from
                            the exporting process's persistent compile cache
      manifest.json         written LAST, checkpoint-style: its presence
                            marks the bundle complete. Input specs, bucket
                            geometry, jax/jaxlib versions, platform,
                            fused-kernel + xla-flags fingerprint, and
                            sha256 + size per file.

Verify-on-load contract (mirrors ``utils/serialization.py`` checkpoints):
``load_bundle`` re-hashes every file against the manifest and checks the
environment fingerprint; any mismatch raises the typed
:class:`ArtifactIncompatible` — the serving layer catches it and falls back
to ordinary trace+compile (a logged degradation, never a dead replica).

This file is the ONE sanctioned loader for artifact payloads (lint rule
BDL012): modules deserialize through ``jax.export.deserialize`` (a
StableHLO parser — no arbitrary code execution) and the manifest through
``json`` — ``pickle`` never touches artifact bytes.
"""

from __future__ import annotations

import json
import logging
import os
import shutil
from typing import Any, Dict, List, Optional, Tuple

import jax

from .serialization import file_digest

log = logging.getLogger("bigdl_tpu.utils.aot")

ARTIFACT_FORMAT = 1
MANIFEST = "manifest.json"

__all__ = [
    "ARTIFACT_FORMAT",
    "ArtifactIncompatible",
    "BundleWriter",
    "environment_fingerprint",
    "export_jit",
    "load_bundle",
    "load_exported",
    "seed_from_bundle",
    "warm_start",
]


class ArtifactIncompatible(Exception):
    """An artifact bundle cannot be used by this process: corrupt/truncated
    payload, environment mismatch (jax/jaxlib version, platform, fused-kernel
    or XLA-flags fingerprint), or geometry drift between the bundle and the
    registering model. Carries a human-readable ``reason``; the serving layer
    logs it and falls back to trace mode."""

    def __init__(self, bundle: str, reason: str):
        self.bundle = bundle
        self.reason = reason
        super().__init__(f"artifact bundle {bundle}: {reason}")


# --------------------------------------------------------------- fingerprint
def environment_fingerprint() -> Dict[str, Any]:
    """What must match between exporter and loader for the bundle's compiled
    programs to be the programs this process would build: library versions,
    backend platform, local device count (the mesh the executables were
    compiled against), and the trace-time knobs that change the lowered
    module (fused kernels, managed XLA flags, compute dtype)."""
    import jaxlib

    from .engine import Engine

    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": jax.default_backend(),
        "local_devices": jax.local_device_count(),
        "fused_kernels": bool(Engine.fused_kernels()),
        "xla_flags": dict(Engine.xla_flags() or {}),
        "compute_dtype": Engine.compute_dtype(),
        "activation_dtype": Engine.activation_dtype(),
    }


def check_fingerprint(bundle: str, manifest: Dict[str, Any]) -> None:
    """Raise :class:`ArtifactIncompatible` when the bundle's environment
    fingerprint does not match this process's."""
    want = manifest.get("fingerprint")
    if not isinstance(want, dict):
        raise ArtifactIncompatible(bundle, "manifest carries no fingerprint")
    have = environment_fingerprint()
    for key, have_val in have.items():
        want_val = want.get(key)
        if want_val != have_val:
            raise ArtifactIncompatible(
                bundle,
                f"environment fingerprint mismatch on {key!r}: bundle has "
                f"{want_val!r}, this process has {have_val!r}",
            )


# -------------------------------------------------------------------- export
def export_jit(fn, specs) -> bytes:
    """Serialize the lowered StableHLO module of jitted ``fn`` against the
    positional arg ``specs`` (a tuple of ShapeDtypeStruct pytrees) via
    ``jax.export``. The module embeds shapes, dtypes, donation and sharding
    — deserializing + calling it replays the exact traced program without
    re-tracing the python model."""
    from jax import export as jexport

    return jexport.export(fn)(*specs).serialize()


def spec_tree(args) -> Tuple:
    """ShapeDtypeStructs mirroring a tuple of array pytrees — the export-time
    record of a compiled function's input geometry. Metadata only: never
    touches buffer contents, so it is safe on donated arrays.

    COMMITTED shardings ride along (uncommitted arrays record none): pjit
    keys on committedness, so an SPMD step lowered against bare shape/dtype
    specs would be a DIFFERENT program than the one the driver dispatches
    with committed batches — the export-time twin compile and the serialized
    module must both reproduce the dispatch-time program exactly. WEAK TYPES
    ride along for the same reason (a Python scalar argument such as the
    step number): without them ``fn.lower(*specs)`` is another program to
    JAX, traced and lowered afresh (11 s of a language model's launch on the
    chip's host) and compiled or loaded from the cache a second time; with
    them it is the dispatch's own lowering, executable included."""

    def spec(a):
        sharding = (
            a.sharding
            if getattr(a, "_committed", False)
            and getattr(a, "sharding", None) is not None
            else None
        )
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding,
                                    weak_type=getattr(a, "weak_type", False))

    return jax.tree_util.tree_map(spec, args)


class BundleWriter:
    """Stages bundle payloads, then commits the manifest LAST.

    Usage::

        w = BundleWriter(path, kind="serving")
        w.add_module("m1.v1.b16", blob)      # bytes -> modules/m1.v1.b16.jexp
        w.harvest_cache()                     # active compile cache -> cache/
        manifest = w.commit(models={...})     # hashes + manifest.json (atomic)

    A crash before ``commit`` leaves no ``manifest.json`` — loaders treat the
    bundle as absent, exactly like a checkpoint without its manifest."""

    def __init__(self, path: str, *, kind: str):
        self.path = path
        self.kind = kind
        self._files: Dict[str, Tuple[str, int]] = {}
        self.cache_entries = 0
        os.makedirs(os.path.join(path, "modules"), exist_ok=True)
        # a PREVIOUS bundle at this path must not bleed stale payloads into
        # the new manifest: drop its completeness marker first, then clear
        # the staged dirs
        try:
            os.remove(os.path.join(path, MANIFEST))
        except OSError:
            pass
        for sub in ("modules", "cache"):
            d = os.path.join(path, sub)
            if os.path.isdir(d):
                for name in os.listdir(d):
                    try:
                        os.remove(os.path.join(d, name))
                    except OSError:
                        pass

    def add_module(self, name: str, blob: bytes) -> str:
        rel = os.path.join("modules", f"{name}.jexp")
        full = os.path.join(self.path, rel)
        with open(full + ".tmp", "wb") as f:
            f.write(blob)
        os.replace(full + ".tmp", full)
        self._files[rel] = file_digest(full)
        return rel

    def harvest_cache(self) -> int:
        """Copy the ACTIVE persistent compile cache's entries into the
        bundle — the payload that makes a replica's warmup compiles disk
        reads. 0 entries (no cache configured) is recorded honestly; the
        bundle then only accelerates boots through its serialized modules."""
        from .compat import harvest_compile_cache

        dest = os.path.join(self.path, "cache")
        self.cache_entries = harvest_compile_cache(dest)
        if os.path.isdir(dest):
            for name in os.listdir(dest):
                rel = os.path.join("cache", name)
                self._files[rel] = file_digest(os.path.join(self.path, rel))
        return self.cache_entries

    def commit(self, **meta) -> Dict[str, Any]:
        import time

        manifest: Dict[str, Any] = {
            "format": ARTIFACT_FORMAT,
            "kind": self.kind,
            "created": time.time(),
            "fingerprint": environment_fingerprint(),
            "cache_entries": self.cache_entries,
        }
        manifest.update(meta)
        manifest["files"] = {
            rel: {"sha256": sha, "bytes": size}
            for rel, (sha, size) in sorted(self._files.items())
        }
        mpath = os.path.join(self.path, MANIFEST)
        with open(mpath + ".tmp", "w") as f:
            json.dump(manifest, f, indent=1)
        os.replace(mpath + ".tmp", mpath)
        return manifest


# ---------------------------------------------------------------------- load
def load_bundle(path: str, *, check_env: bool = True) -> Dict[str, Any]:
    """The verified loader: manifest presence + format + per-file sha256/size
    + (by default) the environment fingerprint. Returns the manifest dict;
    every failure mode raises :class:`ArtifactIncompatible` with the reason
    an operator needs."""
    mpath = os.path.join(path, MANIFEST)
    if not os.path.isdir(path):
        raise ArtifactIncompatible(path, "bundle directory does not exist")
    if not os.path.exists(mpath):
        raise ArtifactIncompatible(
            path, "manifest.json missing (incomplete or interrupted export)"
        )
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise ArtifactIncompatible(path, f"manifest.json unreadable: {e}")
    if manifest.get("format") != ARTIFACT_FORMAT:
        raise ArtifactIncompatible(
            path,
            f"manifest format {manifest.get('format')!r} != supported "
            f"{ARTIFACT_FORMAT}",
        )
    for rel, want in manifest.get("files", {}).items():
        full = os.path.join(path, rel)
        if not os.path.exists(full):
            raise ArtifactIncompatible(path, f"{rel} is missing")
        try:
            sha, size = file_digest(full)
        except OSError as e:
            # payload I/O faults (NFS flake, permissions) are a bundle
            # problem, not a replica-killing one: typed, so the serving
            # degrade policy catches it
            raise ArtifactIncompatible(path, f"{rel} unreadable: {e}")
        if size != want.get("bytes"):
            raise ArtifactIncompatible(
                path,
                f"{rel} is {size} bytes, manifest says {want.get('bytes')} "
                "(truncated?)",
            )
        if sha != want.get("sha256"):
            raise ArtifactIncompatible(path, f"{rel} content checksum mismatch")
    if check_env:
        check_fingerprint(path, manifest)
    return manifest


def load_exported(path: str, rel: str, manifest: Dict[str, Any]):
    """Deserialize one manifest-listed module after re-verifying its hash
    (defense in depth for bundles mutated AFTER ``load_bundle``); returns a
    ``jax.export.Exported``."""
    from jax import export as jexport

    want = manifest.get("files", {}).get(rel)
    if want is None:
        raise ArtifactIncompatible(path, f"{rel} not listed in manifest")
    full = os.path.join(path, rel)
    try:
        sha, size = file_digest(full)
    except OSError as e:
        raise ArtifactIncompatible(path, f"{rel} unreadable: {e}")
    if sha != want.get("sha256") or size != want.get("bytes"):
        raise ArtifactIncompatible(path, f"{rel} content checksum mismatch")
    with open(full, "rb") as f:
        blob = f.read()
    try:
        return jexport.deserialize(bytearray(blob))
    except Exception as e:
        raise ArtifactIncompatible(path, f"{rel} failed to deserialize: {e}")


def seed_from_bundle(path: str, manifest: Optional[Dict[str, Any]] = None) -> int:
    """Copy the bundle's harvested compile-cache entries into this process's
    ACTIVE cache dir (``Engine.ensure_compilation_cache`` is applied first)
    so every warmup/step compile replays as a disk read. Returns the number
    of entries copied (already-present entries are skipped)."""
    from .compat import seed_compile_cache
    from .engine import Engine

    if manifest is None:
        manifest = load_bundle(path)
    src = os.path.join(path, "cache")
    if not os.path.isdir(src):
        return 0
    Engine.ensure_compilation_cache()
    try:
        return seed_compile_cache(src)
    except OSError as e:  # disk full / permissions mid-copy: typed, degradable
        raise ArtifactIncompatible(path, f"cache seeding failed: {e}")


def warm_start(path: str, kind: Optional[str] = None) -> Dict[str, Any]:
    """Verify a bundle end-to-end and seed this process's compile cache from
    it; returns the manifest. The one-call replica warm start for trainers
    (``Optimizer.warm_start``) and scripts; ``ModelServer.warm_start`` wraps
    it with the serving fall-back-to-trace policy. Raises
    :class:`ArtifactIncompatible` — callers own the degrade decision.
    ``kind`` additionally rejects the wrong bundle flavor (a serving
    bundle's cache cannot cover a train step, and vice versa) BEFORE any
    seeding, so a mismatch leaves the cache dir untouched."""
    manifest = load_bundle(path)
    if kind is not None and manifest.get("kind") != kind:
        raise ArtifactIncompatible(
            path,
            f"bundle kind {manifest.get('kind')!r} is not a {kind!r} bundle",
        )
    n = seed_from_bundle(path, manifest)
    log.info(
        "warm start from %s: %d compile-cache entr%s seeded, kind=%s",
        path, n, "y" if n == 1 else "ies", manifest.get("kind"),
    )
    return manifest


# ------------------------------------------------------------- trainer bundle
def export_step_bundle(path: str, *, fn, specs, path_type: str,
                       extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Trainer-side bundle: the cached jitted train step's serialized module
    (when ``jax.export`` can express it — SPMD steps on exotic meshes may
    refuse, in which case the bundle still carries the compile-cache entries,
    which alone deliver the 0-fresh-compile resume) + the cache harvest +
    manifest. Returns the manifest."""
    w = BundleWriter(path, kind="train_step")
    module_rel = None
    export_error = None
    try:
        blob = export_jit(fn, specs)
        module_rel = w.add_module("train_step", blob)
    except Exception as e:  # jax.export coverage gap, not a bundle failure
        export_error = f"{type(e).__name__}: {e}"
        log.warning(
            "train step module export failed (%s); bundle will carry only "
            "the compile-cache entries — the resume still hits 0 fresh "
            "compiles, it just re-traces", export_error,
        )
    w.harvest_cache()
    flat_specs, _ = jax.tree_util.tree_flatten(specs)
    return w.commit(
        step={
            "path_type": path_type,
            "module": module_rel,
            "export_error": export_error,
            "arg_specs": [
                {"shape": list(s.shape), "dtype": str(s.dtype)}
                for s in flat_specs
            ],
            **(extra or {}),
        },
    )
