"""AOT artifact E2E acceptance (ISSUE 11).

Serving: export artifacts from a running 2-model ModelServer, point the
compile cache at a FRESH (empty) dir, boot a second server from the bundle —
telemetry proves 0 fresh bucket compiles, warmup wall-time >=10x below the
traced boot measured in the same test, and predictions bit-identical to the
exporting server.

Trainer: ``export_step_artifact`` after a checkpointed fit -> simulated
preemption -> resume on a fresh ``JAX_COMPILATION_CACHE_DIR`` seeded from the
bundle reaches the next step with 0 fresh compiles (telemetry-proven:
every compile record says ``cache_hit`` and the cache dir gained no entry)
and bit-identical params.

"Fresh boot" is simulated in-process: switching
``Engine.set_compilation_cache_dir`` resets jax's persistent-cache state
(see ``utils/compat.enable_persistent_compilation_cache``), and every
Predictor/optimizer builds fresh jit functions, so cold boots really trace
and compile — the same mechanism ``tools/check.sh --artifacts`` gates.
"""

import importlib.util
import os
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from bigdl_tpu import nn
from bigdl_tpu.obs import JsonlExporter, Telemetry
from bigdl_tpu.serving import ModelServer
from bigdl_tpu.utils import compat
from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.random import RandomGenerator
from bigdl_tpu.utils.serialization import flatten_pytree

REPO = Path(__file__).resolve().parent.parent

spec = importlib.util.spec_from_file_location(
    "obs_report", REPO / "tools" / "obs_report.py"
)
obs_report = importlib.util.module_from_spec(spec)
sys.modules[spec.name] = obs_report
spec.loader.exec_module(obs_report)


@pytest.fixture
def cache_sandbox(tmp_path):
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


def _deep_mlp():
    """Deep enough that XLA compile dominates the warmup (the ratio the
    acceptance asserts is compile-vs-disk-read, so the model must make the
    compile the story, as any real serving model does)."""
    RandomGenerator.set_seed(7)
    layers = []
    for _ in range(80):
        layers += [nn.Linear(256, 256), nn.Tanh()]
    m = nn.Sequential(*layers, nn.Linear(256, 8), nn.LogSoftMax())
    m.init(sample_input=np.zeros((1, 256), np.float32))
    return m


def _deep_seq():
    """Bucketed sequence model (variable-length int records, buckets pad to
    8/16) with a deep head — per-(model, bucket) executables."""
    RandomGenerator.set_seed(13)
    layers = [nn.LookupTable(50, 64), nn.Mean(dimension=2)]
    for _ in range(24):
        layers += [nn.Linear(64, 64), nn.Tanh()]
    return nn.Sequential(*layers, nn.Linear(64, 3), nn.LogSoftMax())


def _mlp_records(n=6):
    gen = np.random.default_rng(3)
    return [gen.standard_normal(256).astype(np.float32) for _ in range(n)]


def _seq_records(n=6):
    gen = np.random.default_rng(4)
    return [gen.integers(1, 50, int(l)).astype(np.int32)
            for l in np.linspace(3, 15, n)]


def _register_both(server, mlp, seq, **kw):
    server.register("mlp", mlp, sample_input=_mlp_records(1)[0],
                    batch_size=4, **kw)
    server.register("seq", seq, sample_input=_seq_records(1)[0],
                    batch_size=4, shape_buckets=(8, 16), **kw)


def _warmups(telemetry):
    return {r["model"]: r for r in telemetry.ring.records
            if r.get("type") == "warmup"}


def test_serving_export_wipe_warm_start(tmp_path, cache_sandbox):
    bundle = str(tmp_path / "bundle")

    # ---- boot 1: traced, against an empty cache dir -----------------------
    cache_sandbox("cache_cold")
    s1 = ModelServer()
    _register_both(s1, _deep_mlp(), _deep_seq())
    w1 = _warmups(s1.telemetry)
    cold_wall = sum(r["seconds"] for r in w1.values())
    assert all(r["warm_start"] is False for r in w1.values())
    assert all(r["fresh_compiles"] > 0 for r in w1.values()), (
        "the traced boot against an empty cache dir must persist fresh "
        "entries — otherwise the warm/cold comparison below compares nothing"
    )
    gold_mlp = np.asarray(s1.predict("mlp", _mlp_records()))
    gold_seq = np.asarray(s1.predict("seq", _seq_records()))
    s1.export_artifacts(bundle)
    s1.close()

    # ---- boot 2: from the bundle, on a FRESH (empty) cache dir ------------
    warm_cache = cache_sandbox("cache_fresh")
    assert os.listdir(warm_cache) == []  # genuinely starting from nothing
    events = tmp_path / "events.jsonl"
    tel = Telemetry(exporters=[JsonlExporter(str(events))])
    s2 = ModelServer(telemetry=tel)
    s2.warm_start(bundle)
    _register_both(s2, _deep_mlp(), _deep_seq(), artifacts=bundle)
    w2 = _warmups(tel)

    # 0 fresh bucket compiles, telemetry-proven, per model
    assert all(r["warm_start"] is True for r in w2.values())
    assert all(r["fresh_compiles"] == 0 for r in w2.values()), (
        f"warm boot wrote fresh cache entries: {w2}"
    )
    # every compile event of the warm boot was a persistent-cache read
    compiles = [r for r in tel.ring.records if r.get("type") == "compile"]
    assert compiles and all(c.get("cache_hit") is True for c in compiles)

    # >=10x lower warmup wall-time, measured in the same test
    warm_wall = sum(r["seconds"] for r in w2.values())
    assert warm_wall * 10 <= cold_wall, (
        f"warm boot {warm_wall:.3f}s vs traced {cold_wall:.3f}s — "
        f"ratio {cold_wall / warm_wall:.1f}x < 10x"
    )

    # every (model, bucket) geometry is served by an installed AOT module
    info = s2.models()
    assert info["mlp"]["aot_modules"] == 1
    assert info["seq"]["aot_modules"] == 2  # one per bucket

    # predictions bit-identical to the exporting server
    got_mlp = np.asarray(s2.predict("mlp", _mlp_records()))
    got_seq = np.asarray(s2.predict("seq", _seq_records()))
    np.testing.assert_array_equal(got_mlp, gold_mlp)
    np.testing.assert_array_equal(got_seq, gold_seq)
    s2.close()

    # the live stream schema-validates and the report renders the boot
    records = obs_report.load(str(events))
    summary = obs_report.summarize(records)
    assert summary["warmup"]["all_cache_hits"] is True
    assert summary["warmup"]["warm_start"] is True
    assert summary["warmup"]["total_fresh_compiles"] == 0
    rendered = obs_report.render(summary)
    assert "cold start" in rendered and "[artifact warm start]" in rendered

    # run_start carries the bundle path (the stream is self-describing)
    start = next(r for r in records
                 if r["type"] == "meta" and r.get("event") == "run_start")
    assert start.get("warm_start") == bundle


def test_serving_hot_swap_keeps_aot(tmp_path, cache_sandbox):
    """A same-architecture hot-swap inherits the installed AOT modules: the
    new version's warmup re-uses the already-compiled wrappers (params are
    arguments, not constants, in the exported programs)."""
    bundle = str(tmp_path / "bundle")
    cache_sandbox("c1")
    s1 = ModelServer()
    s1.register("m", _deep_mlp(), sample_input=_mlp_records(1)[0],
                batch_size=4)
    s1.export_artifacts(bundle)
    s1.close()

    cache_sandbox("c2")
    s2 = ModelServer()
    s2.register("m", _deep_mlp(), sample_input=_mlp_records(1)[0],
                batch_size=4, artifacts=bundle)
    assert s2.models()["m"]["aot_modules"] == 1
    v2_model = _deep_mlp()  # same architecture, fresh weights
    watch = compat.CacheDirWatch()
    s2.update("m", v2_model)
    assert s2.models()["m"]["aot_modules"] == 1  # modules survived the swap
    assert watch.delta() == set()  # swap warmup compiled nothing fresh
    # the swapped version serves ITS weights through the inherited module
    got = np.asarray(s2.predict("m", _mlp_records(2)))
    from bigdl_tpu.optim.predictor import Predictor

    want = np.asarray(Predictor(v2_model, batch_size=4).predict(
        np.stack(_mlp_records(2))
    ))
    np.testing.assert_array_equal(got, want)
    s2.close()


def _trainer_parts(tel=None):
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.optim import LocalOptimizer

    RandomGenerator.set_seed(11)
    gen = np.random.default_rng(5)
    x = gen.standard_normal((64, 16)).astype(np.float32)
    y = gen.integers(0, 4, 64)
    opt = LocalOptimizer(
        nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 4),
                      nn.LogSoftMax()),
        DataSet.array(x, y, batch_size=16),
        nn.ClassNLLCriterion(),
    )
    if tel is not None:
        opt.set_telemetry(tel)
    return opt


def _params(model):
    return {k: np.array(v)
            for k, v in flatten_pytree(model.get_parameters()).items()}


# The trainer phases run in REAL subprocesses: that is the faithful
# preemption story (a preempted run resumes in a NEW process on a new host).
_TRAINER_PROBE = """
import json, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
phase, kind, ckpt, bundle, cache, out = sys.argv[1:7]
assert os.environ["JAX_COMPILATION_CACHE_DIR"] == cache  # placed by the parent
import numpy as np
from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.obs.telemetry import Telemetry
from bigdl_tpu.optim import LocalOptimizer, Trigger
from bigdl_tpu.utils import compat
from bigdl_tpu.utils.random import RandomGenerator
from bigdl_tpu.utils.serialization import flatten_pytree

def parts(tel=None, donate=True):
    RandomGenerator.set_seed(11)
    gen = np.random.default_rng(5)
    if kind == "distri":
        from bigdl_tpu.parallel import DistriOptimizer
        x = gen.standard_normal((64, 12)).astype(np.float32)
        y = gen.integers(0, 3, 64)
        opt = DistriOptimizer(
            nn.Sequential(nn.Linear(12, 16), nn.Tanh(), nn.Linear(16, 3),
                          nn.LogSoftMax()),
            DataSet.array(x, y, batch_size=16), nn.ClassNLLCriterion(),
            parameter_sync="sharded", donate=donate)
    else:
        x = gen.standard_normal((64, 16)).astype(np.float32)
        y = gen.integers(0, 4, 64)
        opt = LocalOptimizer(
            nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 4),
                          nn.LogSoftMax()),
            DataSet.array(x, y, batch_size=16), nn.ClassNLLCriterion(),
            donate=donate)
    if tel is not None:
        opt.set_telemetry(tel)
    return opt

def dump_params(opt):
    np.savez(out, **flatten_pytree(opt.model.get_parameters()))

if phase == "export":
    opt = parts()
    opt.set_checkpoint(ckpt, trigger=Trigger.several_iteration(3))
    opt.set_end_when(Trigger.max_iteration(3))
    opt.optimize()
    man = opt.export_step_artifact(bundle)
    print(json.dumps({"kind": man["kind"],
                      "path_type": man["step"]["path_type"],
                      "module": man["step"]["module"],
                      "cache_entries": man["cache_entries"]}))
elif phase == "gold":
    # the oracle runs donation-free: numerics are donation-invariant, so
    # the donated warm resume must match it bit for bit
    opt = parts(donate=False)
    opt.resume(ckpt)
    opt.set_end_when(Trigger.max_iteration(4))
    opt.optimize()
    dump_params(opt)
    print(json.dumps({"ok": True}))
elif phase == "warm":
    tel = Telemetry()
    opt = parts(tel)
    opt.warm_start(bundle)
    before = compat.compilation_cache_entries()
    opt.resume(ckpt)
    opt.set_end_when(Trigger.max_iteration(4))
    opt.optimize()
    after = compat.compilation_cache_entries()
    dump_params(opt)
    start = next(r for r in tel.ring.records
                 if r["type"] == "meta" and r.get("event") == "run_start")
    print(json.dumps({
        "fresh": sorted(after - before),
        "compiles": [r.get("cache_hit") for r in tel.ring.records
                     if r.get("type") == "compile"],
        "warm_start": start.get("warm_start"),
    }))
"""


def _run_trainer_phase(phase, kind, ckpt, bundle, cache, out):
    import json
    import subprocess

    env = {**os.environ, "PYTHONPATH": str(REPO),
           "JAX_COMPILATION_CACHE_DIR": cache}
    proc = subprocess.run(
        [sys.executable, "-c", _TRAINER_PROBE, phase, kind, ckpt, bundle,
         cache, out],
        capture_output=True, text=True, timeout=300, env=env, cwd=str(REPO),
    )
    assert proc.returncode == 0, f"{phase}/{kind}: {proc.stderr[-2000:]}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _trainer_resume_matrix(tmp_path, kind):
    ckpt = str(tmp_path / "ckpt")
    bundle = str(tmp_path / "bundle")
    c1, c2 = str(tmp_path / "host1"), str(tmp_path / "host2")
    os.makedirs(c1), os.makedirs(c2)

    # host 1: fit + checkpoint + export the step artifact
    man = _run_trainer_phase("export", kind, ckpt, bundle, c1,
                             str(tmp_path / "unused.npz"))
    assert man["kind"] == "train_step"
    assert man["cache_entries"] > 0
    if kind == "local":
        assert man["path_type"] == "LocalOptimizer"
        assert man["module"] == "modules/train_step.jexp"

    # gold continuation: a fresh process on the SAME host (same cache dir)
    gold_out = str(tmp_path / "gold.npz")
    _run_trainer_phase("gold", kind, ckpt, bundle, c1, gold_out)

    # preempted -> fresh host: EMPTY cache dir seeded only from the bundle
    assert os.listdir(c2) == []
    got_out = str(tmp_path / "got.npz")
    res = _run_trainer_phase("warm", kind, ckpt, bundle, c2, got_out)
    assert res["fresh"] == [], (
        f"resumed fit persisted fresh entries: {res['fresh']}"
    )
    assert res["compiles"], "the resumed fit must still RECORD its compile"
    assert all(h is True for h in res["compiles"])
    assert res["warm_start"] == bundle

    gold = np.load(gold_out)
    got = np.load(got_out)
    assert sorted(gold.files) == sorted(got.files)
    for k in gold.files:
        np.testing.assert_array_equal(gold[k], got[k], err_msg=k)


def test_trainer_export_preempt_resume_zero_fresh(tmp_path):
    _trainer_resume_matrix(tmp_path, "local")


def test_trainer_step_module_exported(tmp_path, cache_sandbox):
    """The local step exports a serialized module (not just the cache): the
    bundle's train_step.jexp deserializes through the verified loader."""
    from bigdl_tpu.optim import Trigger
    from bigdl_tpu.utils import aot

    cache_sandbox("mod")
    bundle = str(tmp_path / "bundle")
    opt = _trainer_parts()
    opt.set_end_when(Trigger.max_iteration(2))
    opt.optimize()
    manifest = opt.export_step_artifact(bundle)
    assert manifest["step"]["module"] == "modules/train_step.jexp"
    assert manifest["step"]["export_error"] is None
    exported = aot.load_exported(
        bundle, manifest["step"]["module"], aot.load_bundle(bundle)
    )
    # 9-arg local step signature, donation recorded on the carried state
    assert len(manifest["step"]["arg_specs"]) >= 9
    assert exported.in_avals


@pytest.mark.slow
def test_distri_step_artifact_resume(tmp_path):
    """ZeRO-1 sharded DistriOptimizer: export at the cached-step seam (the
    SPMD module may or may not be jax.export-expressible — either way the
    bundle's cache entries alone must deliver the 0-fresh-compile resume),
    same three-process matrix as the local path."""
    _trainer_resume_matrix(tmp_path, "distri")
