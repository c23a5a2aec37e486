"""Test harness config: run everything on a virtual 8-device CPU platform.

This is the analog of the reference's local[4] SparkContext trick (SURVEY.md §4):
real distributed semantics without a cluster. Must set env before jax import.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

# Tier-1 does not fit its 870 s window with a cold compile cache: ~7,900 tiny
# eager-op executables cost ~1,270 s cold (the parent tree: 1,180 s) against
# ~550 s warm on this host (measured for PR 21). The suite therefore PLACES
# its cache from outside, the way any launcher may: jax's own variable, one
# fixed path that outlives a fresh checkout. setdefault: a caller's placement
# wins. The library's unplaced default (<checkout>/.jax_cache) is what
# tests/test_compile_cache.py checks in subprocesses with the variable unset.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      "/tmp/bigdl_tpu_tier1_jax_cache")

# Backends initialize lazily, so this still lands even if a pytest plugin
# imported jax before this file set the environment above.
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed():
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(42)
    np.random.seed(42)
    yield


@pytest.fixture(autouse=True, scope="module")
def _engine_topology_stays_in_its_module():
    """A module that freezes the 8-device Engine topology and does not reset
    it failed whichever serving test xdist placed after it on the same worker
    ("batch_size 4 not divisible by 8 devices"): a different test each run."""
    yield
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()


@pytest.fixture
def rng():
    import jax

    return jax.random.PRNGKey(0)
