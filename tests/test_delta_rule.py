"""The chunked gated delta rule (``ops/delta_rule.py``) against the recurrence
token by token (the plain reference's, ``models/delta_hybrid_lm_reference``),
on the CPU: values and the gradients of all five inputs, at
a record shorter than one chunk, of whole chunks and with a ragged last
chunk, with beta up to 2 (negative eigenvalues) and decays from near 0 to
near 1; the heads in groups against all at once; and the form compiled at
the cell's widths for a described v5e."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import delta_hybrid_lm_reference as ref
from bigdl_tpu.ops import delta_rule as dr
from bigdl_tpu.ops import ssd


def _inputs(t, h=3, dk=8, dv=16, n=2, seed=0):
    """q and k L2-normed; log alpha from about -4.5 (alpha 0.01: forgotten
    within a token) to -3e-4 (alpha 0.9997); beta = 2 sigmoid(.) spread over
    (0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (n, t, h, dk)))
    k = unit(jax.random.normal(ks[1], (n, t, h, dk)))
    v = jax.random.normal(ks[2], (n, t, h, dv))
    log_alpha = -jnp.exp(jax.random.uniform(ks[3], (n, t, h), minval=-8.0,
                                            maxval=1.5))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (n, t, h)))
    return q, k, v, log_alpha, beta


def _sequential(q, k, v, log_alpha, beta, segment=None):
    """The reference's recurrence over each record: ``delta_rule``'s
    arguments without the chunk, in segments of ``segment`` tokens (the
    whole record by default)."""
    return jax.vmap(lambda *a: ref.recurrence(
        *a, segment=segment or a[0].shape[0]))(
            q, k, v, jnp.exp(log_alpha), beta)


SHAPES = {"below-one-chunk": (5, 8), "whole-chunks": (32, 8),
          "ragged-last-chunk": (29, 8)}
NAMES = ("q", "k", "v", "log_alpha", "beta")


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


@pytest.fixture(scope="module", params=list(SHAPES))
def runs(request):
    t, chunk = SHAPES[request.param]
    args = _inputs(t)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    every = tuple(range(len(args)))
    return dict(
        args=args, o=dr.delta_rule(*args, chunk=chunk),
        want=_sequential(*args),
        grads=jax.grad(lambda *a: jnp.sum(
            w * dr.delta_rule(*a, chunk=chunk)), every)(*args),
        rgrads=jax.grad(lambda *a: jnp.sum(
            w * _sequential(*a)), every)(*args))


def test_the_inputs_span_beta_to_two_and_decays_to_zero_and_one(runs):
    _, _, _, log_alpha, beta = runs["args"]
    assert float(beta.max()) > 1.9 and float(beta.min()) < 0.1
    assert float(jnp.mean(beta > 1.0)) > 0.3
    alpha = jnp.exp(log_alpha)
    assert float(alpha.min()) < 0.05 and float(alpha.max()) > 0.999


def test_chunked_rule_gives_the_recurrences_values(runs):
    assert runs["o"].dtype == jnp.float32
    assert runs["o"].shape == runs["want"].shape
    np.testing.assert_allclose(runs["o"], runs["want"], atol=1e-5)


@pytest.mark.parametrize("i", range(len(NAMES)), ids=NAMES)
def test_chunked_rule_gradient_matches_the_recurrences(runs, i):
    got, want = runs["grads"][i], runs["rgrads"][i]
    assert got.shape == want.shape
    assert _rel(got, want) < 1e-5


def test_a_write_above_one_turns_the_state_along_its_key():
    """beta 1.5 on one repeated key and no value after the first token: the
    state's part along k is multiplied by 1 - 1.5 = -0.5 every token, so the
    read along k changes sign token by token (an eigenvalue below 0)."""
    t = 6
    k = jnp.zeros((1, t, 1, 4)).at[..., 0].set(1.0)
    v = jnp.zeros((1, t, 1, 2)).at[0, 0, 0, 0].set(1.0)
    beta = jnp.full((1, t, 1), 1.5)
    o = dr.delta_rule(k, k, v, jnp.zeros((1, t, 1)), beta, chunk=4)
    want = 1.5 * (-0.5) ** jnp.arange(t)
    np.testing.assert_allclose(o[0, :, 0, 0], want, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(o, _sequential(
        k, k, v, jnp.zeros((1, t, 1)), beta), atol=1e-6)


def test_heads_in_groups_give_what_all_heads_at_once_give(monkeypatch):
    args = _inputs(24, h=6)
    w = jax.random.normal(jax.random.PRNGKey(3), args[2].shape)
    loss = lambda *a: jnp.sum(w * dr.delta_rule(*a, chunk=8))  # noqa: E731
    whole = dr.delta_rule(*args, chunk=8)
    g_whole = jax.grad(loss, (0, 4))(*args)
    assert dr.head_group(2, 24, 6, 8, 16) == 6
    # room for two heads' [K, V]: 2 records x 24 tokens x 24 float32 a head
    monkeypatch.setattr(ssd, "_GROUP_BYTES", 2 * 2 * 24 * 24 * 4)
    assert dr.head_group(2, 24, 6, 8, 16) == 2
    grouped = dr.delta_rule(*args, chunk=8)
    np.testing.assert_allclose(grouped, whole, atol=1e-6)
    for a, b in zip(jax.grad(loss, (0, 4))(*args), g_whole):
        assert _rel(a, b) < 1e-6


def test_the_head_group_at_the_cells_widths():
    """One record of 8192 tokens, 30 heads of keys 96 and values 192: a
    head's float32 [K, V] is 9.4 MB, so six heads a group (five groups)."""
    assert dr.head_group(1, 8192, 30, 96, 192) == 6
    assert dr.head_group(1, 8192, 1, 96, 192) == 1


def test_segments_of_the_recurrence_give_the_whole_records_answer():
    args = _inputs(32)
    np.testing.assert_allclose(_sequential(*args, segment=8),
                               _sequential(*args), atol=1e-6)


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip: the TPU's compiler without the TPU."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_rule_compiles_at_the_cells_widths_for_a_v5e(one_chip):
    """Forward and backward at one record of 8192 tokens, 30 heads of keys
    96 and values 192, bfloat16 operands: the triangular solve and the scan
    compile for the chip, and the groups hold the temporaries to 1.5 GB
    (4.1 GB with all heads at once)."""
    from jax.experimental.compilation_cache import compilation_cache

    from bigdl_tpu.utils.engine import Engine

    n, t, h, dk, dv = 1, 8192, 30, 96, 192
    shape = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.float32, sharding=one_chip)

    def fwd_bwd(q, k, v, la, b, g):
        _, vjp = jax.vjp(lambda *a: dr.delta_rule(*a), q, k, v, la, b)
        return vjp(g)

    prev = Engine.compute_dtype()
    was = jax.config.jax_enable_compilation_cache
    Engine.set_compute_dtype("bfloat16")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(fwd_bwd).lower(
            shape(n, t, h, dk), shape(n, t, h, dk), shape(n, t, h, dv),
            shape(n, t, h), shape(n, t, h), shape(n, t, h, dv)).compile()
    finally:
        Engine.set_compute_dtype(prev)
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6e9
    assert math.isfinite(compiled.memory_analysis().temp_size_in_bytes)
