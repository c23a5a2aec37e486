"""Persistent-compilation-cache placement: one resolver, one rule.

Where ``JAX_COMPILATION_CACHE_DIR`` is set jax has already read it and the
program leaves the directory alone; where it is not, the cache goes to the
fixed ``<checkout>/.jax_cache``. The cache config is process-global jax
state, so the round trips run in subprocesses: a cold run populates the
cache dir, a restarted process must report a hit (no new entries written) —
the mechanism ``chip_smoke.py --expect-cache-hit`` relies on.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_PROBE = """
import os, sys, json
import jax
updates = []
_update = jax.config.update
jax.config.update = lambda k, v: (updates.append((k, v)), _update(k, v))[1]
import numpy as np
from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger
from bigdl_tpu.utils import compat
from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.random import RandomGenerator

if len(sys.argv) > 1:  # stand-in for <checkout>/.jax_cache (see _run)
    compat.DEFAULT_COMPILE_CACHE_DIR = sys.argv[1]
RandomGenerator.set_seed(5)
rng = np.random.default_rng(0)
x = rng.standard_normal((32, 6)).astype(np.float32)
y = rng.integers(0, 2, 32)
opt = LocalOptimizer(
    nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 2), nn.LogSoftMax()),
    DataSet.array(x, y, batch_size=16), nn.ClassNLLCriterion())
before = compat.compilation_cache_entries()
opt.set_end_when(Trigger.max_iteration(2))
opt.optimize()
after = compat.compilation_cache_entries()
print(json.dumps({
    "dir": Engine.compilation_cache_dir(),
    "jax_dir": jax.config.jax_compilation_cache_dir,
    "dir_updates": [v for k, v in updates if k == "jax_compilation_cache_dir"],
    "hit": compat.compilation_cache_hit(before, after),
    "entries": len(after),
}))
"""


def _run(cache_dir=None, default_dir=None):
    env = {**os.environ, "PYTHONPATH": str(REPO), "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    # default_dir keeps the unset-variable case off the real in-checkout
    # cache; the probe installs it as compat.DEFAULT_COMPILE_CACHE_DIR
    argv = [] if default_dir is None else [str(default_dir)]
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE, *argv],
        capture_output=True, text=True, timeout=240, env=env, cwd=str(REPO),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_restarted_process_hits_cache(tmp_path):
    cache = tmp_path / "xla_cache"
    cold = _run(cache)
    assert cold["dir"] == str(cache)
    assert cold["hit"] is False
    assert cold["entries"] > 0  # the train step was persisted
    warm = _run(cache)
    assert warm["hit"] is True  # same executable served from disk
    assert warm["entries"] == cold["entries"]


def test_variable_set_leaves_directory_alone(tmp_path):
    """The directory placed from outside is never re-pointed: jax read the
    variable at import, and the program issues no jax_compilation_cache_dir
    update of its own."""
    cache = tmp_path / "placed"
    out = _run(cache)
    assert out["jax_dir"] == str(cache) == out["dir"]
    assert out["dir_updates"] == []


def test_variable_unset_uses_the_fixed_default(tmp_path):
    default = tmp_path / "default_cache"
    out = _run(default_dir=default)
    assert out["dir"] == out["jax_dir"] == str(default)
    assert out["dir_updates"] == [str(default)]
    assert out["entries"] > 0


def test_default_is_one_fixed_path_inside_the_checkout(monkeypatch):
    from bigdl_tpu.utils import compat

    assert compat.DEFAULT_COMPILE_CACHE_DIR == str(REPO / ".jax_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compat.resolve_compilation_cache_dir()
    # nothing about the process or the moment may leak into the path
    monkeypatch.setenv("TMPDIR", "/somewhere/else")
    monkeypatch.setattr(os, "getpid", lambda: 424242)
    monkeypatch.setattr(os, "getuid", lambda: 4242)
    assert compat.resolve_compilation_cache_dir() == first == str(
        REPO / ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compat.resolve_compilation_cache_dir() == "/some/dir"


def test_no_second_name_for_the_cache_dir():
    """jax's variable is the only name: the repo's own spellings are gone
    from every source, script and document."""
    old = ("BIGDL_COMPILE_" + "CACHE_DIR", "BENCH_COMPILE_" + "CACHE_DIR")
    hits = []
    for path in REPO.rglob("*"):
        if path.suffix not in (".py", ".sh", ".md") or not path.is_file():
            continue
        rel = path.relative_to(REPO)
        if rel.parts[0] in (".git", ".jax_cache", "chiprun_out", ".scratch") \
                or rel.name == "ISSUE.md":
            continue
        text = path.read_text(errors="replace")
        hits += [f"{rel}: {name}" for name in old if name in text]
    assert hits == []


def test_cache_helpers_without_cache_configured():
    from bigdl_tpu.utils import compat

    # the no-cache snapshot contract: entries() returns None when no
    # persistent cache is configured, and hit(None, None) must be inert
    assert compat.compilation_cache_hit(None, None) is False
    assert compat.compilation_cache_hit(None, {"x"}) is False


def test_tier1_cache_dir_resolved_and_populated():
    """After a compile-bearing optimizer run the resolved dir must hold
    persisted executables — proof the wiring is live in-process."""
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.optim import LocalOptimizer, Trigger
    from bigdl_tpu.utils.engine import Engine

    rng = np.random.default_rng(3)
    x = rng.standard_normal((32, 6)).astype(np.float32)
    y = rng.integers(0, 2, 32)
    opt = LocalOptimizer(
        nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 2),
                      nn.LogSoftMax()),
        DataSet.array(x, y, batch_size=16), nn.ClassNLLCriterion())
    opt.set_end_when(Trigger.max_iteration(2))
    opt.optimize()  # compile-bearing: the train step lands in the cache
    cache_dir = Engine.compilation_cache_dir()
    assert os.path.isdir(cache_dir) and os.listdir(cache_dir), (
        "persistent compile cache dir is empty after a compile-bearing test"
    )
