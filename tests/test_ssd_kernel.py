"""The chunked scan's Pallas kernels (``ops/ssd_kernel.py``) through
``ops.ssd_scan(..., interpret=True)``, against the XLA form of the same scan
and the recurrence one token at a time; the rule that chooses between the two
forms; and the four kernels compiled at granite-4.0-h-micro's widths for a
described v5e (what the interpreter cannot refuse, Mosaic can)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops import ssd, ssd_kernel
from bigdl_tpu.utils.engine import Engine

SCAN_INPUTS = ("x", "dt", "a", "b", "c", "d")
# name -> (records, tokens, chunk, heads, head_dim, compute dtype)
CASES = {
    "one-chunk": (1, 128, 128, 2, 64, "float32"),
    "several-chunks-two-records": (2, 384, 128, 2, 64, "float32"),
    "ragged-last-chunk-16-heads-of-8": (1, 300, 128, 16, 8, "float32"),
    "two-blocks-a-chunk-4-heads-a-lane-group": (1, 512, 256, 8, 32, "float32"),
    "several-chunks-bfloat16": (1, 256, 128, 2, 64, "bfloat16"),
}
# (kernel against the XLA form, either against the recurrence): relative L2
BANDS = {"float32": (2e-5, 1e-4), "bfloat16": (2e-2, 3e-2)}


def _inputs(t, h, p, s=16, n=1, seed=0):
    """Decays from one to a thousand tokens, head by head, as
    ``tests/test_hybrid_lm.py``'s."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (n, t, h, p))
    rate = jnp.exp(jnp.linspace(math.log(1e-3), 0.0, h))
    a = -jnp.exp(jax.random.uniform(ks[1], (h,), minval=0.0, maxval=math.log(16)))
    dt = rate / -a * jnp.exp(0.3 * jax.random.normal(ks[2], (n, t, h)))
    b = jax.random.normal(ks[3], (n, t, s))
    c = jax.random.normal(ks[4], (n, t, s))
    d = 1.0 + 0.1 * jax.random.normal(ks[5], (h,))
    return x, dt, a, b, c, d


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


@pytest.fixture(scope="module", params=list(CASES))
def scans(request):
    n, t, chunk, h, p, dtype = CASES[request.param]
    args = _inputs(t, h, p, n=n)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    every = tuple(range(len(args)))

    def run(fn):
        return fn(*args), jax.grad(lambda *a: jnp.sum(w * fn(*a)[0]), every)(*args)

    prev = Engine.compute_dtype()
    Engine.set_compute_dtype(dtype)
    try:
        ssd.take_scan_records()
        (y, stats), grads = run(lambda *a: ssd.ssd_scan(*a, chunk=chunk, interpret=True))
        records = ssd.take_scan_records()
        (xla_y, xla_stats), xla_grads = run(lambda *a: ssd.ssd_scan(*a, chunk=chunk))
        xla_records = ssd.take_scan_records()
    finally:
        Engine.set_compute_dtype(prev)
    (want, _), want_grads = run(lambda *a: (ssd.ssd_sequential(*a), None))
    return dict(bands=BANDS[dtype], y=y, stats=stats, grads=grads, records=records,
                xla=(xla_y, xla_stats, xla_grads, xla_records),
                want=want, want_grads=want_grads, shape=(n, t, chunk, h, p))


def test_kernels_give_the_xla_forms_and_the_recurrences_values(scans):
    close, band = scans["bands"]
    assert scans["y"].dtype == jnp.float32
    assert scans["y"].shape == scans["want"].shape
    assert _rel(scans["y"], scans["xla"][0]) < close
    assert _rel(scans["y"], scans["want"]) < band
    assert _rel(scans["xla"][0], scans["want"]) < band


@pytest.mark.parametrize("i", range(len(SCAN_INPUTS)), ids=SCAN_INPUTS)
def test_kernels_gradient_matches_the_xla_forms_and_the_recurrences(scans, i):
    close, band = scans["bands"]
    got, xla, want = scans["grads"][i], scans["xla"][2][i], scans["want_grads"][i]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _rel(got, xla) < 5 * close      # the sums of d cum cancel: a is the tender one
    assert _rel(got, want) < band


def test_scan_statistics_are_the_same_on_both_paths(scans):
    stats, xla = scans["stats"], scans["xla"][1]
    assert float(stats.log_decay_min) == float(xla.log_decay_min)
    assert float(stats.state_sq_sum) == pytest.approx(
        float(xla.state_sq_sum), rel=scans["bands"][0])
    assert stats.state_count == xla.state_count


def test_the_record_says_which_form_ran(scans):
    n, t, chunk, h, p = scans["shape"]
    shape = dict(records=n, tokens=t, chunk=chunk, chunks=-(-t // chunk), heads=h,
                 head_dim=p, state=16, groups=1, group_heads=h)
    kernel, = scans["records"]
    assert kernel == dict(shape, kernel=True, heads_per_step=h, calls=2)
    # the CPU backend without ``interpret`` takes the XLA form of any shape
    xla, = scans["xla"][3]
    assert xla == dict(shape, kernel=False, head_group=h, calls=2)


# ------------------------------------------------------------- which form runs

@pytest.mark.parametrize("heads,head_dim,chunk,state,itemsize,per_step", [
    (64, 64, 256, 128, 2, 16),     # the benchmark's cell: 9.1 MiB of blocks
    (64, 64, 256, 128, 4, 16),     # float32 operands
    (64, 128, 256, 128, 2, 8),     # a head a lane group
    (32, 32, 128, 64, 2, 32),      # four heads a lane group, all heads a step
    (2, 64, 128, 16, 4, 2),        # fewer than 8 heads: all of them
    (64, 64, 64, 128, 2, None),    # a chunk of 64 is no multiple of 128
    (64, 64, 192, 128, 2, None),
    (64, 48, 256, 128, 2, None),   # 48 neither divides 128 nor is a multiple
    (3, 64, 256, 128, 2, None),    # 3 heads of 64 fill no whole lane group
    (12, 64, 256, 128, 2, 12),     # 12 is no multiple of 8, but is all heads
    (64, 64, 256, 12, 2, None),    # the state is no multiple of 8
])
def test_heads_per_step_follows_the_shapes(heads, head_dim, chunk, state,
                                           itemsize, per_step):
    assert ssd_kernel.heads_per_step(
        heads, head_dim, chunk, state, itemsize) == per_step


def test_a_shape_that_does_not_tile_takes_the_xla_form_under_interpret_too():
    args = _inputs(96, 4, 8)
    ssd.take_scan_records()
    y, _ = ssd.ssd_scan(*args, chunk=32, interpret=True)
    record, = ssd.take_scan_records()
    assert record["kernel"] is False and record["head_group"] == 4
    np.testing.assert_allclose(y, ssd.ssd_sequential(*args), atol=5e-6)


def test_a_record_shorter_than_a_chunk_is_one_chunk_of_its_own_length():
    args = _inputs(128, 2, 64)
    ssd.take_scan_records()
    y, _ = ssd.ssd_scan(*args, chunk=256, interpret=True)
    record, = ssd.take_scan_records()
    assert record["kernel"] is True and record["chunk"] == 128
    assert _rel(y, ssd.ssd_sequential(*args)) < 1e-5


# ------------------------------------------- compiled for a described v5e chip

@pytest.fixture(scope="module")
def one_chip():
    """A v5e chip that is described, not attached (``on-chip-measurement``
    guide, section 2): the TPU's compiler refuses here what it would refuse
    on the chip. Made inside the fixture, so that only the worker that runs
    this file loads the TPU's library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


# (records, chunks, chunk, heads, head size, state, B/C groups, heads a step)
CELL_SCANS = {"one_group": (1, 32, 256, 64, 64, 128, 1, 16),    # granite's cell
              "eight_groups": (2, 64, 128, 64, 64, 128, 8, 8)}  # nemotron's


@pytest.mark.parametrize("cell", list(CELL_SCANS))
@pytest.mark.parametrize("name", ["states", "states_bwd", "outputs", "outputs_bwd"])
def test_kernels_compile_for_a_v5e_at_the_cells_widths(one_chip, no_compile_cache,
                                                       name, cell):
    n, nc, q, h, p, s, g, want = CELL_SCANS[cell]
    per_step = ssd_kernel.heads_per_step(h, p, q, s, 2, g)
    assert per_step == want

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    x, rows, bc = spec(n, nc * q, h * p), spec(n, nc, h, q), spec(n, nc, g, q, s)
    cb, entering, d = spec(n, nc, g, q, q), spec(n, nc, s, h * p), spec(1, h * p)
    states = lambda *a: ssd_kernel.chunk_states(*a, per_step, False)  # noqa: E731
    outputs = lambda *a: ssd_kernel.chunk_outputs(*a, per_step, False)  # noqa: E731
    fn, args = {
        "states": (states, (x, rows, bc)),
        "states_bwd": (lambda *a: jax.vjp(states, *a[:-1])[1](a[-1]),
                       (x, rows, bc, entering)),
        "outputs": (outputs, (cb, rows, rows, x, bc, entering, d)),
        "outputs_bwd": (lambda *a: jax.vjp(outputs, *a[:-1])[1](a[-1]),
                        (cb, rows, rows, x, bc, entering, d, x)),
    }[name]
    prev = Engine.compute_dtype()
    Engine.set_compute_dtype("bfloat16")
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    finally:
        Engine.set_compute_dtype(prev)
    assert "tpu_custom_call" in compiled.as_text()
