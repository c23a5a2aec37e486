"""The framework's seams onto jax: the Pallas launch helper, the float8
capability probe, the per-chip peaks table and the persistent compile cache.

Written for the one installation ``pyproject.toml`` pins (jax/jaxlib 0.9.0):
there is no branch here for a jax that is not installed."""

from __future__ import annotations

import os

import jax


def pallas_interpret_default() -> bool:
    """Whether Pallas kernels run in interpret mode for the current trace:
    off the TPU there is no Mosaic compiler, so the kernels execute as their
    jnp-level interpretation — slower, but numerically the same program.
    This is what lets tier-1 exercise every kernel under JAX_PLATFORMS=cpu.

    ``BIGDL_PALLAS_INTERPRET=0|1`` overrides the backend rule off the TPU —
    the resolution is TRACE-time, so a CPU-hosted cross-lowering for the TPU
    platform (the program-size threshold tests) forces ``0`` to get the real
    Mosaic custom call into the lowered module. On the ``tpu`` backend the
    kernels always compile: asking for interpret mode there is an error
    (see :func:`pallas_call`), not a slower correct answer."""
    forced = os.environ.get("BIGDL_PALLAS_INTERPRET")
    if forced:
        return forced.lower() in ("1", "true", "yes", "on")
    return jax.default_backend() != "tpu"


def pallas_call(kernel, *, interpret=None, **kwargs):
    """The ONE sanctioned ``pl.pallas_call`` entry point (lint rule BDL009).

    ``interpret=None`` resolves via :func:`pallas_interpret_default`, so every
    kernel runs interpreted off the TPU and compiled by Mosaic on it. An
    explicit bool is forwarded — except that interpret mode on the ``tpu``
    backend raises: a kernel that silently ran as its jnp expansion there
    would pass every numeric check while proving nothing about Mosaic."""
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = pallas_interpret_default()
    if interpret and jax.default_backend() == "tpu":
        raise RuntimeError(
            "Pallas interpret mode requested on the tpu backend "
            "(interpret=True or BIGDL_PALLAS_INTERPRET=1): kernels compile "
            "through Mosaic here; interpret mode is for CPU hosts only"
        )
    return pl.pallas_call(kernel, interpret=interpret, **kwargs)  # lint: disable=BDL009 the helper IS the sanctioned entry


# --------------------------------------------------------------------------
# low-precision dtype availability (float8) — the capability probe behind
# every ``comms_dtype=`` / ``master_dtype=`` / ``quantize="fp8"`` knob
# --------------------------------------------------------------------------

# canonical public spellings accepted by the low-precision policy knobs;
# values are the jnp attribute that backs each (resolved lazily so an old
# stack without float8 still imports this module)
_PRECISION_DTYPE_ATTRS = {
    "bfloat16": "bfloat16",
    "int8": "int8",
    "float8_e4m3": "float8_e4m3fn",
    "float8_e4m3fn": "float8_e4m3fn",
    "float8_e5m2": "float8_e5m2",
}


class Float8Support:
    """Typed capability probe result for float8 on the active jax/jaxlib/
    ml_dtypes stack: ``available`` plus either the resolved dtype map or the
    human-readable ``reason`` the stack lacks them. The probe is behavioral
    (a tiny cast must round-trip), not just an attribute check — a jnp that
    exposes the symbol but whose XLA rejects the conversion counts as
    unavailable."""

    __slots__ = ("available", "dtypes", "reason")

    def __init__(self, available: bool, dtypes=None, reason=None):
        self.available = bool(available)
        self.dtypes = dict(dtypes or {})
        self.reason = reason

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        if self.available:
            return f"Float8Support(available=True, dtypes={sorted(self.dtypes)})"
        return f"Float8Support(available=False, reason={self.reason!r})"


_float8_probe_cache = None


def probe_float8(refresh: bool = False) -> Float8Support:
    """Probe (once per process) whether float8_e4m3fn / float8_e5m2 exist and
    actually convert on this stack. Every fp8-accepting knob routes its
    availability decision through here so an unsupported stack produces ONE
    consistent, typed answer — a clean ``ValueError`` at the policy surface,
    never an AttributeError/import crash from deep inside a trace."""
    global _float8_probe_cache
    if _float8_probe_cache is not None and not refresh:
        return _float8_probe_cache
    import jax.numpy as jnp
    import numpy as np

    dtypes = {}
    try:
        for name in ("float8_e4m3fn", "float8_e5m2"):
            dt = getattr(jnp, name, None)
            if dt is None:
                raise AttributeError(f"jax.numpy lacks {name}")
            # behavioral check: the cast must survive a host round-trip
            back = np.asarray(jnp.asarray([0.5, -2.0], dtype=dt).astype(jnp.float32))
            if not np.allclose(back, [0.5, -2.0]):
                raise ValueError(f"{name} cast does not round-trip: {back}")
            dtypes[name] = dt
        support = Float8Support(True, dtypes=dtypes)
    except Exception as e:  # typed probe: the reason travels to the ValueError
        support = Float8Support(False, reason=f"{type(e).__name__}: {e}")
    _float8_probe_cache = support
    return support


def resolve_precision_dtype(name, knob: str = "comms_dtype"):
    """Map a policy-knob dtype spelling (``"bfloat16"``, ``"int8"``,
    ``"float8_e4m3"``/``"float8_e4m3fn"``, ``"float8_e5m2"``, or an actual
    dtype) to the canonical jnp dtype. ``None`` passes through (policy off).
    Raises ``ValueError`` — never an import/attribute crash — when the name
    is unknown or names a float8 type on a stack without float8 support
    (:func:`probe_float8` supplies the reason)."""
    if name is None:
        return None
    import jax.numpy as jnp
    import numpy as np

    if not isinstance(name, str):
        name = np.dtype(name).name
    key = name.lower()
    attr = _PRECISION_DTYPE_ATTRS.get(key)
    if attr is None:
        raise ValueError(
            f"{knob}={name!r} is not a supported low-precision dtype; "
            f"choose one of {sorted(set(_PRECISION_DTYPE_ATTRS))}"
        )
    if attr.startswith("float8"):
        support = probe_float8()
        if not support.available:
            raise ValueError(
                f"{knob}={name!r} requires float8 support, which this "
                f"jax/jaxlib/ml_dtypes stack lacks ({support.reason}); use "
                "'bfloat16' or 'int8' instead"
            )
        return support.dtypes[attr]
    return getattr(jnp, attr)


# --------------------------------------------------------------------------
# per-backend hardware peaks — the MFU/roofline denominator table
# --------------------------------------------------------------------------

class DevicePeaks:
    """Public-spec peaks of one chip kind: bf16 matmul ``flops`` (flops/s),
    ``hbm_bytes_s`` (HBM bandwidth, bytes/s) and ``ici_bytes_s`` (interchip
    interconnect, bytes/s per chip)."""

    __slots__ = ("kind", "flops", "hbm_bytes_s", "ici_bytes_s")

    def __init__(self, kind, flops=None, hbm_bytes_s=None, ici_bytes_s=None):
        self.kind = kind
        self.flops = flops
        self.hbm_bytes_s = hbm_bytes_s
        self.ici_bytes_s = ici_bytes_s

    def __repr__(self):  # pragma: no cover - debugging nicety
        return (f"DevicePeaks({self.kind!r}, flops={self.flops!r}, "
                f"hbm={self.hbm_bytes_s!r}, ici={self.ici_bytes_s!r})")


# bf16 peak matmul TFLOP/s, HBM GB/s and per-chip ICI GB/s by device_kind
# substring: the table behind the live obs/perf.py step records' MFU and
# chip_smoke.py's device check.
# Source: Google Cloud TPU documentation, one page per generation ("TPU v5e":
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s = 200 GB/s ICI; likewise "TPU
# v2" ... "TPU v6e"). device_kind spells v5e as "TPU v5 lite".
_DEVICE_PEAKS = {
    "v2":      (45.0,  700.0,  62.5),
    "v3":      (123.0, 900.0,  81.0),
    "v4":      (275.0, 1228.0, 300.0),
    "v5e":     (197.0, 819.0,  200.0),
    "v5 lite": (197.0, 819.0,  200.0),
    "v5lite":  (197.0, 819.0,  200.0),
    "v5p":     (459.0, 2765.0, 600.0),
    "v6e":     (918.0, 1640.0, 448.0),
}


def device_peaks(device_kind=None):
    """Resolve a device kind (default: the first local device of the active
    backend) to its :class:`DevicePeaks`.

    The CPU backend has no peaks and yields ``None`` (tier-1 and
    ``obs/perf.py`` read that as "no MFU, roofline unclassified"). An
    accelerator whose kind is not in the table raises and names the kind: a
    utilization figure divided by a guessed or missing peak is worse than
    none, so an unknown chip is added to ``_DEVICE_PEAKS`` with its source,
    never defaulted."""
    if device_kind is None:
        device_kind = jax.local_devices()[0].device_kind
    kind = str(device_kind).lower()
    if kind == "cpu":  # how the CPU backend spells its device_kind
        return None
    # longest key first so "v5e"/"v5p"/"v5 lite" beat the bare "v5" prefix
    for key in sorted(_DEVICE_PEAKS, key=len, reverse=True):
        if key in kind:
            tflops, hbm_gbs, ici_gbs = _DEVICE_PEAKS[key]
            return DevicePeaks(
                device_kind,
                flops=tflops * 1e12,
                hbm_bytes_s=hbm_gbs * 1e9,
                ici_bytes_s=ici_gbs * 1e9,
            )
    raise ValueError(
        f"no peak FLOP/s / bandwidth entry for device kind {device_kind!r}; "
        "add it to utils/compat._DEVICE_PEAKS with its published source"
    )


# the one place an unplaced cache goes: <checkout>/.jax_cache (gitignored).
# A FIXED path on purpose — a cache whose directory is minted per run never
# hits, so nothing here is built from TMPDIR, a uid, a pid or a clock
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def resolve_compilation_cache_dir() -> str:
    """Where this process's persistent compile cache lives — one rule:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (jax's own
    variable, the only name there is), else the fixed in-checkout
    :data:`DEFAULT_COMPILE_CACHE_DIR`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_COMPILE_CACHE_DIR


def enable_persistent_compilation_cache(cache_dir=None) -> str:
    """Turn on jax's persistent compilation cache; returns the active dir.

    ``cache_dir=None`` applies :func:`resolve_compilation_cache_dir`. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set jax has already read it into
    ``jax_compilation_cache_dir`` at import: the directory is left exactly
    as placed from outside and only the thresholds below are set. An
    explicit ``cache_dir`` is the mid-process switch the AOT-artifact tests
    use to simulate a fresh boot (``Engine.set_compilation_cache_dir``).

    Three contracts ride on top of pointing the directory:

    * **Persist everything.** jax's default thresholds skip fast/small
      compiles, which on CPU-sized test graphs would cache nothing — and a
      cold compile that persisted nothing would read as a hit.
    * **Relocatable cache keys.** jax 0.9.0 still points the XLA autotune
      cache INSIDE the compile cache dir (``compiler.get_compile_options``)
      and does not strip that path from the cache key, so entries copied to
      a differently-spelled dir would never hit.
      ``jax_persistent_cache_enable_xla_caches`` is forced empty (a GPU-only
      feature anyway), making the key a pure function of (program, versions,
      flags): entries harvested into an artifact bundle seed ANY dir.
    * **Unlatching.** jax latches "cache unused" at the first compile of the
      process; configuring after any jnp op has compiled would otherwise
      silently disable persistence for the process's whole life.
      :func:`reset_compilation_cache` unlatches it — this is also what lets
      one process switch cache dirs.
    """
    cache_dir = cache_dir or resolve_compilation_cache_dir()
    if jax.config.jax_compilation_cache_dir != cache_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError as e:
        raise RuntimeError(
            f"cannot create the compile cache dir {cache_dir!r}; set "
            "JAX_COMPILATION_CACHE_DIR to a writable directory"
        ) from e
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "")
    reset_compilation_cache()
    return cache_dir


def reset_compilation_cache() -> None:
    """Drop jax's in-memory persistent-cache state so the configured dir is
    (re-)read on the next compile (see "Unlatching" above)."""
    from jax._src.compilation_cache import reset_cache

    reset_cache()


def compilation_cache_entries():
    """Names of the persisted executables in the active cache dir, or ``None``
    when no persistent cache is configured. Snapshot before compiling, then
    diff with :func:`compilation_cache_hit` to tell a cache hit from a cold
    compile — the telemetry ``compile`` record's ``cache_hit`` field."""
    d = jax.config.jax_compilation_cache_dir
    if not d or not os.path.isdir(d):
        return None
    # jax's LRUCache writes '<key>-cache' + '<key>-atime' pairs; the
    # access-time markers are rewritten on every read, so only entries count
    return {f for f in os.listdir(d) if not f.endswith("-atime")}


def compilation_cache_hit(before, after):
    """True when a compile between the two snapshots wrote no new cache entry
    into a previously non-empty cache — i.e. the executable was served from
    disk rather than rebuilt. False with no cache configured (every compile
    is cold)."""
    if before is None or after is None:
        return False
    return bool(before) and not (after - before)


class CacheDirWatch:
    """Incremental persistent-cache-dir snapshot: ``observe()`` answers "did
    the compile(s) since the last call write fresh entries, or were they
    served from disk?" — the per-compile ``cache_hit`` telemetry field and
    the artifact warm-boot proof both ride on it.

    One ``os.listdir`` per call; callers only invoke it when a compile was
    actually detected (jit-cache growth), so the steady-state hot loop never
    pays it."""

    def __init__(self):
        self._snap = compilation_cache_entries()

    def delta(self):
        """Entry names added since the last call (snapshot updates), or
        ``None`` when no persistent cache is configured."""
        now = compilation_cache_entries()
        if now is None or self._snap is None:
            self._snap = now
            return None
        new = now - self._snap
        self._snap = now
        return new

    def observe(self):
        """``True`` = the compile(s) since last call hit the persistent cache
        (no fresh entries written), ``False`` = at least one fresh entry was
        persisted (a cold compile), ``None`` = no cache dir configured."""
        new = self.delta()
        return None if new is None else not new

    def fresh_count(self):
        """Number of fresh entries since the last call, or ``None`` when no
        cache dir is configured."""
        new = self.delta()
        return None if new is None else len(new)


def _copy_cache_entries(src: str, dest: str, skip_existing: bool) -> int:
    """Copy persistent-cache entries between directories, excluding the
    LRU's access-time markers (the receiving LRU recreates them); the ONE
    walk shared by harvest (cache → bundle) and seed (bundle → cache), so
    the entry-name conventions cannot drift between the two directions."""
    import shutil

    os.makedirs(dest, exist_ok=True)
    n = 0
    for name in os.listdir(src):
        if name.endswith("-atime"):
            continue
        target = os.path.join(dest, name)
        if skip_existing and os.path.exists(target):
            continue
        shutil.copy2(os.path.join(src, name), target)
        n += 1
    return n


def harvest_compile_cache(dest_dir: str) -> int:
    """Copy every entry of the ACTIVE persistent compile cache into
    ``dest_dir``; returns the number of entries copied. 0 when no cache is
    configured. The artifact bundle's ``cache/`` payload."""
    src = jax.config.jax_compilation_cache_dir
    if not src or not os.path.isdir(src):
        return 0
    return _copy_cache_entries(src, dest_dir, skip_existing=False)


def seed_compile_cache(src_dir: str) -> int:
    """Copy cache entries from ``src_dir`` into the ACTIVE persistent compile
    cache dir (entries already present are left untouched — a shared store
    seeding many replicas must not rewrite concurrently-read files); returns
    the number of entries copied. Raises ``RuntimeError`` when no cache dir
    is configured yet (``Engine.ensure_compilation_cache`` has not run): with
    nowhere to put the executables the warm boot CANNOT work, and silently
    pretending it did would masquerade as the trace-everything cold path."""
    dest = jax.config.jax_compilation_cache_dir
    if not dest:
        raise RuntimeError(
            "seed_compile_cache: no persistent compile cache configured — "
            "call Engine.ensure_compilation_cache() before warm-starting "
            "from an artifact bundle"
        )
    return _copy_cache_entries(src_dir, dest, skip_existing=True)


def prune_compile_cache(cache_dir: str, max_bytes=None, max_age_days=None):
    """Bound a persistent compile cache dir: drop entries older than
    ``max_age_days`` (by access time — the LRU's ``-atime`` marker when
    present, else the entry's own mtime), then least-recently-used entries
    until the remaining total is under ``max_bytes``. Returns the pruned
    entry names. Long-lived hosts and shared artifact stores otherwise grow
    without bound — one entry per distinct executable, forever."""
    import time as _time

    if not os.path.isdir(cache_dir):
        return []
    entries = {}
    for name in os.listdir(cache_dir):
        if name.endswith("-atime"):
            continue
        path = os.path.join(cache_dir, name)
        try:
            st = os.stat(path)
        except OSError:  # raced with another pruner
            continue
        atime_path = path + "-atime"
        try:
            used = os.stat(atime_path).st_mtime
        except OSError:
            used = st.st_mtime
        entries[name] = (used, st.st_size)
    doomed = []
    now = _time.time()
    if max_age_days is not None:
        cutoff = now - float(max_age_days) * 86400.0
        doomed.extend(n for n, (used, _) in entries.items() if used < cutoff)
    if max_bytes is not None:
        kept = sorted(
            ((used, n) for n, (used, _) in entries.items() if n not in doomed),
        )
        total = sum(entries[n][1] for _, n in kept)
        for used, n in kept:
            if total <= int(max_bytes):
                break
            doomed.append(n)
            total -= entries[n][1]
    for name in doomed:
        for victim in (name, name + "-atime"):
            try:
                os.remove(os.path.join(cache_dir, victim))
            except OSError:  # already gone / race with another pruner
                pass
    return doomed
