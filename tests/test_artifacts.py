"""AOT artifact bundles (utils/aot.py + serving/artifacts.py): bundle
round-trip, the corruption/incompatibility matrix (every failure mode ->
typed ``ArtifactIncompatible`` + graceful fall-back-to-trace with the server
alive and bit-identical to a cold boot), compile-cache hygiene
(``prune_compile_cache``), and the unwarmed-model warn satellite."""

import json
import os
import shutil

import numpy as np
import pytest

import jax

from bigdl_tpu import nn
from bigdl_tpu.serving import ArtifactIncompatible, ModelServer
from bigdl_tpu.utils import aot, compat
from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.random import RandomGenerator


@pytest.fixture
def cache_sandbox(tmp_path):
    """Switch the persistent compile cache to per-test dirs and restore the
    suite-wide dir afterwards. ``use("name")`` activates a fresh dir — the
    in-process analogue of booting on a new host with an empty
    JAX_COMPILATION_CACHE_DIR (jax's in-memory cache state is reset at each
    switch by ``enable_persistent_compilation_cache``)."""
    prev_dir = Engine.compilation_cache_dir()

    def use(name: str) -> str:
        d = str(tmp_path / name)
        os.makedirs(d, exist_ok=True)
        Engine.set_compilation_cache_dir(d)
        jax.clear_caches()
        return d

    yield use
    if prev_dir:
        Engine.set_compilation_cache_dir(prev_dir)
    jax.clear_caches()


def _tiny_model(seed=5):
    RandomGenerator.set_seed(seed)
    m = nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 3))
    m.init(sample_input=np.zeros((1, 6), np.float32))
    return m


def _record():
    return np.arange(6, dtype=np.float32) / 6.0


def _export_tiny_bundle(tmp_path, cache_sandbox, name="m"):
    cache_sandbox("cache_export")
    bundle = str(tmp_path / "bundle")
    with ModelServer() as server:
        server.register(name, _tiny_model(), sample_input=_record(),
                        batch_size=4)
        manifest = server.export_artifacts(bundle)
    return bundle, manifest


# ------------------------------------------------------------- bundle basics
class TestBundle:
    def test_round_trip_and_layout(self, tmp_path, cache_sandbox):
        bundle, manifest = _export_tiny_bundle(tmp_path, cache_sandbox)
        assert os.path.exists(os.path.join(bundle, "manifest.json"))
        assert manifest["kind"] == "serving"
        assert manifest["cache_entries"] > 0
        assert "m" in manifest["models"]
        entry = manifest["models"]["m"]
        assert entry["batch_size"] == 4
        assert entry["record_trailing"] == [6]
        assert list(entry["modules"]) == ["fixed"]
        # verified load passes and every listed file hash-verifies
        loaded = aot.load_bundle(bundle)
        assert loaded["models"] == manifest["models"]
        # module deserializes through the sanctioned loader
        exported = aot.load_exported(
            bundle, entry["modules"]["fixed"], loaded
        )
        assert tuple(exported.in_avals[-1].shape) == (4, 6)

    def test_manifest_written_last(self, tmp_path, cache_sandbox):
        """An interrupted export (no manifest) must read as ABSENT, exactly
        like a checkpoint without its manifest."""
        bundle, _ = _export_tiny_bundle(tmp_path, cache_sandbox)
        os.remove(os.path.join(bundle, "manifest.json"))
        with pytest.raises(ArtifactIncompatible, match="manifest.json missing"):
            aot.load_bundle(bundle)

    def test_fingerprint_gate(self, tmp_path, cache_sandbox):
        bundle, _ = _export_tiny_bundle(tmp_path, cache_sandbox)
        mpath = os.path.join(bundle, "manifest.json")
        man = json.load(open(mpath))
        man["fingerprint"]["jaxlib"] = "0.0.1-not-this-one"
        with open(mpath, "w") as f:
            json.dump(man, f)
        with pytest.raises(ArtifactIncompatible, match="jaxlib"):
            aot.load_bundle(bundle)
        # env check is opt-out for tools that only inspect payloads
        assert aot.load_bundle(bundle, check_env=False)["kind"] == "serving"

    def test_export_without_models_refuses(self, cache_sandbox, tmp_path):
        cache_sandbox("c")
        with ModelServer() as server:
            with pytest.raises(ValueError, match="no models registered"):
                server.export_artifacts(str(tmp_path / "b"))


# ------------------------------------------------- corruption / drift matrix
class TestCorruptionMatrix:
    """Each corruption yields ArtifactIncompatible internally, a logged
    ``warn`` telemetry record, a server that STAYS ALIVE in trace mode, and
    predictions bit-identical to a cold boot."""

    def _boot_with(self, bundle, cache_sandbox, tag, **register_kw):
        cache_sandbox(f"cache_{tag}")
        server = ModelServer()
        server.register("m", _tiny_model(), sample_input=_record(),
                        batch_size=4, artifacts=bundle, **register_kw)
        return server

    def _assert_fell_back(self, server, gold):
        info = server.models()["m"]
        assert info["aot_modules"] == 0  # trace mode, not a dead replica
        warns = [r for r in server.telemetry.ring.records
                 if r.get("type") == "warn"
                 and r.get("reason") == "artifact_incompatible"]
        assert warns, "fallback must be visible in the telemetry stream"
        assert warns[0].get("detail")
        out = server.predict("m", [_record(), _record() * 0.5])
        np.testing.assert_array_equal(np.asarray(out), gold)
        server.close()

    @pytest.fixture
    def gold(self, tmp_path, cache_sandbox):
        bundle, _ = _export_tiny_bundle(tmp_path, cache_sandbox)
        cache_sandbox("cache_gold")
        with ModelServer() as server:  # cold boot, no artifacts: the oracle
            server.register("m", _tiny_model(), sample_input=_record(),
                            batch_size=4)
            out = np.asarray(server.predict("m", [_record(), _record() * 0.5]))
        return bundle, out

    def test_truncated_cache_entry(self, gold, cache_sandbox):
        bundle, oracle = gold
        cache_dir = os.path.join(bundle, "cache")
        victim = os.path.join(cache_dir, sorted(os.listdir(cache_dir))[0])
        with open(victim, "r+b") as f:
            f.truncate(max(1, os.path.getsize(victim) // 2))
        self._assert_fell_back(
            self._boot_with(bundle, cache_sandbox, "trunc"), oracle
        )

    def test_tampered_hash(self, gold, cache_sandbox):
        bundle, oracle = gold
        mpath = os.path.join(bundle, "manifest.json")
        man = json.load(open(mpath))
        rel = next(iter(man["files"]))
        man["files"][rel]["sha256"] = "0" * 64
        with open(mpath, "w") as f:
            json.dump(man, f)
        self._assert_fell_back(
            self._boot_with(bundle, cache_sandbox, "hash"), oracle
        )

    def test_jaxlib_version_mismatch(self, gold, cache_sandbox):
        bundle, oracle = gold
        mpath = os.path.join(bundle, "manifest.json")
        man = json.load(open(mpath))
        man["fingerprint"]["jaxlib"] = "9.9.9"
        with open(mpath, "w") as f:
            json.dump(man, f)
        self._assert_fell_back(
            self._boot_with(bundle, cache_sandbox, "ver"), oracle
        )

    def test_bucket_geometry_drift(self, gold, cache_sandbox):
        bundle, oracle = gold
        # registration asks for a different batch geometry than the bundle
        cache_sandbox("cache_geom")
        server = ModelServer()
        server.register("m", _tiny_model(), sample_input=_record(),
                        batch_size=8, artifacts=bundle)
        info = server.models()["m"]
        assert info["aot_modules"] == 0
        warns = [r for r in server.telemetry.ring.records
                 if r.get("type") == "warn"
                 and r.get("reason") == "artifact_incompatible"]
        assert warns and "geometry drift" in warns[0]["detail"]
        out = server.predict("m", [_record(), _record() * 0.5])
        np.testing.assert_array_equal(np.asarray(out), oracle)
        server.close()

    def test_architecture_drift_same_record_shape(self, gold, cache_sandbox):
        """A widened model with the SAME record geometry passes the
        record-level check but must still be caught (module in_avals vs the
        registering model's params/state signature) — typed fallback, not an
        untyped pytree error killing the registration."""
        bundle, _ = gold
        cache_sandbox("cache_arch")
        RandomGenerator.set_seed(6)
        wider = nn.Sequential(nn.Linear(6, 12), nn.Tanh(), nn.Linear(12, 3))
        wider.init(sample_input=np.zeros((1, 6), np.float32))
        server = ModelServer()
        server.register("m", wider, sample_input=_record(), batch_size=4,
                        artifacts=bundle)
        info = server.models()["m"]
        assert info["aot_modules"] == 0  # fell back to trace mode
        warns = [r for r in server.telemetry.ring.records
                 if r.get("type") == "warn"
                 and r.get("reason") == "artifact_incompatible"]
        assert warns and "signature mismatch" in warns[0]["detail"]
        out = server.predict("m", [_record()])  # alive and serving
        assert np.asarray(out).shape == (1, 3)
        server.close()

    def test_missing_manifest(self, gold, cache_sandbox):
        bundle, oracle = gold
        os.remove(os.path.join(bundle, "manifest.json"))
        self._assert_fell_back(
            self._boot_with(bundle, cache_sandbox, "noman"), oracle
        )

    def test_unknown_model_in_bundle(self, gold, cache_sandbox):
        bundle, _ = gold
        cache_sandbox("cache_unknown")
        server = ModelServer()
        server.register("other", _tiny_model(), sample_input=_record(),
                        batch_size=4, artifacts=bundle)
        assert server.models()["other"]["aot_modules"] == 0
        warns = [r for r in server.telemetry.ring.records
                 if r.get("type") == "warn"
                 and r.get("reason") == "artifact_incompatible"]
        assert warns and "no artifacts for model" in warns[0]["detail"]
        server.close()

    def test_strict_warm_start_raises(self, gold, cache_sandbox):
        bundle, _ = gold
        os.remove(os.path.join(bundle, "manifest.json"))
        cache_sandbox("cache_strict")
        with ModelServer() as server:
            with pytest.raises(ArtifactIncompatible):
                server.warm_start(bundle)


# ------------------------------------------------------------ cache hygiene
class TestPruneCompileCache:
    def _mk_entry(self, d, name, size, age_s, atime=True):
        path = os.path.join(d, name)
        with open(path, "wb") as f:
            f.write(b"x" * size)
        import time

        old = time.time() - age_s
        os.utime(path, (old, old))
        if atime:
            with open(path + "-atime", "w"):
                pass
            os.utime(path + "-atime", (old, old))

    def test_age_prune(self, tmp_path):
        d = str(tmp_path)
        self._mk_entry(d, "old", 10, 10 * 86400)
        self._mk_entry(d, "new", 10, 60)
        pruned = compat.prune_compile_cache(d, max_age_days=5)
        assert pruned == ["old"]
        assert sorted(os.listdir(d)) == ["new", "new-atime"]

    def test_size_prune_lru_order(self, tmp_path):
        d = str(tmp_path)
        self._mk_entry(d, "oldest", 100, 3000)
        self._mk_entry(d, "mid", 100, 2000)
        self._mk_entry(d, "newest", 100, 1000)
        pruned = compat.prune_compile_cache(d, max_bytes=250)
        # least-recently-used goes first, newest survives
        assert pruned == ["oldest"]
        remaining = {f for f in os.listdir(d) if not f.endswith("-atime")}
        assert remaining == {"mid", "newest"}

    def test_entry_without_atime_uses_mtime(self, tmp_path):
        d = str(tmp_path)
        self._mk_entry(d, "bare", 10, 10 * 86400, atime=False)
        assert compat.prune_compile_cache(d, max_age_days=1) == ["bare"]
        assert os.listdir(d) == []

    def test_noop_within_bounds(self, tmp_path):
        d = str(tmp_path)
        self._mk_entry(d, "a", 10, 60)
        assert compat.prune_compile_cache(d, max_bytes=1000,
                                          max_age_days=30) == []

    def test_missing_dir_is_empty(self, tmp_path):
        assert compat.prune_compile_cache(str(tmp_path / "nope"),
                                          max_bytes=1) == []

    def test_engine_env_call_site(self, tmp_path, monkeypatch):
        """Engine.ensure_compilation_cache prunes once per process when the
        env knobs are set — the long-lived-host hygiene seam."""
        d = str(tmp_path / "cache")
        os.makedirs(d)
        self._mk_entry(d, "ancient", 10, 30 * 86400)
        monkeypatch.setattr(compat, "DEFAULT_COMPILE_CACHE_DIR", d)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("BIGDL_COMPILE_CACHE_MAX_AGE_DAYS", "7")
        prev = Engine.compilation_cache_dir()
        monkeypatch.setattr(Engine, "_cache_pruned", False)
        monkeypatch.setattr(Engine._state, "compilation_cache_dir", None)
        try:
            assert Engine.ensure_compilation_cache() == d
            assert "ancient" not in os.listdir(d)
        finally:
            if prev:
                Engine.set_compilation_cache_dir(prev)


# ----------------------------------------------------------------- watchers
class TestCacheDirWatch:
    def test_observe_classifies_fresh_vs_hit(self, cache_sandbox):
        d = cache_sandbox("watch")
        watch = compat.CacheDirWatch()
        with open(os.path.join(d, "entry-cache"), "wb") as f:
            f.write(b"z")
        assert watch.observe() is False  # a fresh entry appeared: cold
        assert watch.observe() is True  # nothing new since: disk read


# ------------------------------------------------------- unwarmed satellite
class TestUnwarmedWarn:
    def test_register_warmup_false_emits_warn_record(self, cache_sandbox):
        cache_sandbox("warm0")
        with ModelServer() as server:
            server.register("m", _tiny_model(), sample_input=_record(),
                            batch_size=4, warmup=False)
            warns = [r for r in server.telemetry.ring.records
                     if r.get("type") == "warn"
                     and r.get("reason") == "unwarmed_model"]
            assert warns and warns[0]["model"] == "m"

    def test_register_without_sample_emits_warn_record(self, cache_sandbox):
        cache_sandbox("warm1")
        with ModelServer() as server:
            server.register("m", _tiny_model(), batch_size=4)
            warns = [r for r in server.telemetry.ring.records
                     if r.get("type") == "warn"
                     and r.get("reason") == "unwarmed_model"]
            assert warns and warns[0]["model"] == "m"

    def test_warmed_register_emits_no_unwarmed_warn(self, cache_sandbox):
        cache_sandbox("warm2")
        with ModelServer() as server:
            server.register("m", _tiny_model(), sample_input=_record(),
                            batch_size=4)
            assert not [r for r in server.telemetry.ring.records
                        if r.get("type") == "warn"
                        and r.get("reason") == "unwarmed_model"]
            warmups = [r for r in server.telemetry.ring.records
                       if r.get("type") == "warmup"]
            assert len(warmups) == 1 and warmups[0]["model"] == "m"
            assert warmups[0]["warm_start"] is False


# ------------------------------------------------------------- trainer seam
class TestStepArtifactSurface:
    def test_export_before_fit_refuses(self):
        from bigdl_tpu.dataset import DataSet
        from bigdl_tpu.optim import LocalOptimizer

        RandomGenerator.set_seed(2)
        x = np.zeros((8, 6), np.float32)
        y = np.zeros(8, np.int64)
        opt = LocalOptimizer(
            nn.Sequential(nn.Linear(6, 4), nn.LogSoftMax()),
            DataSet.array(x, y, batch_size=8), nn.ClassNLLCriterion(),
        )
        with pytest.raises(RuntimeError, match="run optimize"):
            opt.export_step_artifact("/tmp/never-written")

    def test_trainer_warm_start_rejects_serving_bundle(self, tmp_path,
                                                       cache_sandbox):
        """Kind gate, checked BEFORE seeding: a serving bundle's cache
        cannot cover a train step — accepting it would record a warm start
        while every step compile runs cold."""
        from bigdl_tpu.dataset import DataSet
        from bigdl_tpu.optim import LocalOptimizer

        bundle, _ = _export_tiny_bundle(tmp_path, cache_sandbox)
        fresh = cache_sandbox("kindgate")
        RandomGenerator.set_seed(2)
        x = np.zeros((8, 6), np.float32)
        y = np.zeros(8, np.int64)
        opt = LocalOptimizer(
            nn.Sequential(nn.Linear(6, 4), nn.LogSoftMax()),
            DataSet.array(x, y, batch_size=8), nn.ClassNLLCriterion(),
        )
        with pytest.raises(ArtifactIncompatible, match="train_step"):
            opt.warm_start(bundle)
        assert os.listdir(fresh) == []  # nothing half-seeded
