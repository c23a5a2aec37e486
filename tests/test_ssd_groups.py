"""More than one B/C group in the state-space scan (``ops/ssd.py``, the four
kernels of ``ops/ssd_kernel.py`` in interpret mode): ``ssd_scan`` with 1, 2
and 8 groups against the sequential recurrence, outputs and gradients, in
both forms; the record's new fields; one group bit for bit what it was at the
parent commit; the rules that choose the heads a grid step and the head
group. The kernels compiled for a described v5e at the benchmark cell's
shapes are ``tests/test_ssd_kernel.py``'s last cases."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops import ssd, ssd_kernel


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _scan_inputs(t, h, p, groups, s=16, n=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (n, t, h, p))
    rate = jnp.exp(jnp.linspace(math.log(1e-3), 0.0, h))
    a = -jnp.exp(jax.random.uniform(ks[1], (h,), minval=0.0, maxval=math.log(16)))
    dt = rate / -a * jnp.exp(0.3 * jax.random.normal(ks[2], (n, t, h)))
    b = jax.random.normal(ks[3], (n, t, groups, s))
    c = jax.random.normal(ks[4], (n, t, groups, s))
    d = 1.0 + 0.1 * jax.random.normal(ks[5], (h,))
    return x, dt, a, b, c, d


def _by_hand(x, dt, a, b, c, d):
    """The recurrence in numpy, head by head with its own group's B and C."""
    x, dt, a, b, c, d = (np.asarray(v, np.float64) for v in (x, dt, a, b, c, d))
    n, t, h, p = x.shape
    per_group = h // b.shape[2]
    y = np.zeros_like(x)
    for r in range(n):
        for head in range(h):
            g = head // per_group
            state = np.zeros((p, b.shape[-1]))
            for i in range(t):
                state = state * np.exp(dt[r, i, head] * a[head]) \
                    + dt[r, i, head] * np.outer(x[r, i, head], b[r, i, g])
                y[r, i, head] = state @ c[r, i, g] + d[head] * x[r, i, head]
    return y


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_sequential_recurrence_reads_each_heads_own_group(groups):
    args = _scan_inputs(12, 8, 4, groups, s=8, n=1)
    np.testing.assert_allclose(ssd.ssd_sequential(*args), _by_hand(*args),
                               atol=2e-5)


@pytest.fixture(scope="module", params=[1, 2, 8])
def grouped_scans(request):
    """ssd_scan in both forms and the recurrence, outputs and gradients, at
    64 heads of 16 in ``groups`` B/C groups (the kernels' steps take 8 heads:
    a whole group of 8, a half of 32, an eighth of 64)."""
    groups = request.param
    args = _scan_inputs(256, 64, 16, groups)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    every = tuple(range(len(args)))

    def run(fn):
        return fn(*args), jax.grad(lambda *a: jnp.sum(w * fn(*a)), every)(*args)

    ssd.take_scan_records()
    kernels = run(lambda *a: ssd.ssd_scan(*a, chunk=128, interpret=True)[0])
    record, = ssd.take_scan_records()
    xla = run(lambda *a: ssd.ssd_scan(*a, chunk=128)[0])
    xla_record, = ssd.take_scan_records()
    want = run(lambda *a: ssd.ssd_sequential(*a, segment=64))
    return dict(groups=groups, kernels=kernels, xla=xla, want=want,
                record=record, xla_record=xla_record)


def test_grouped_scan_matches_the_recurrence_in_both_forms(grouped_scans):
    s = grouped_scans
    assert _rel(s["kernels"][0], s["want"][0]) < 1e-4
    assert _rel(s["xla"][0], s["want"][0]) < 1e-4
    assert _rel(s["kernels"][0], s["xla"][0]) < 2e-5


@pytest.mark.parametrize("i", range(6), ids=("x", "dt", "a", "b", "c", "d"))
def test_grouped_scans_gradient_matches_the_recurrences(grouped_scans, i):
    s = grouped_scans
    want = s["want"][1][i]
    assert s["kernels"][1][i].shape == want.shape == s["xla"][1][i].shape
    assert _rel(s["kernels"][1][i], want) < 1e-4
    assert _rel(s["xla"][1][i], want) < 1e-4


def test_the_record_names_the_groups_and_the_heads_of_one(grouped_scans):
    s, g = grouped_scans, grouped_scans["groups"]
    shape = dict(records=2, tokens=256, chunk=128, chunks=2, heads=64,
                 head_dim=16, state=16, groups=g, group_heads=64 // g, calls=2)
    # a grid step's heads lie inside one group: 64, 32, 8 heads a step
    assert s["record"] == dict(shape, kernel=True,
                               heads_per_step=min(64 // g, 64))
    assert s["xla_record"] == dict(
        shape, kernel=False, head_group=ssd.head_group(2, 2, 64, 128, g))


# y's sum, one element, the state's squares, and the sums of the six gradients
# of ssd_scan at ONE B/C group at the parent commit (877eba6), 2 records of
# 256 tokens, 8 heads of 16, chunk 128: the XLA form and the kernels
ONE_GROUP_AT_THE_PARENT = {
    False: ["-0x1.6796460000000p+8", "0x1.df9ebe0000000p+0",
            "0x1.9e39e40000000p+7", "-0x1.2fd6be0000000p+7",
            "0x1.090bb00000000p+12", "0x1.7b63e20000000p+3",
            "-0x1.0abc480000000p+4", "-0x1.2066f80000000p+8",
            "0x1.554ff00000000p+4"],
    True: ["-0x1.6796380000000p+8", "0x1.df9ebe0000000p+0",
           "0x1.9e39d40000000p+7", "-0x1.2fd6a00000000p+7",
           "0x1.090bae0000000p+12", "0x1.7b63da0000000p+3",
           "-0x1.0abc580000000p+4", "-0x1.2066f80000000p+8",
           "0x1.5550a00000000p+4"],
}


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernels"])
@pytest.mark.parametrize("group_axis", [False, True],
                         ids=["b-without-the-axis", "b-with-the-axis"])
def test_one_group_gives_what_it_gave_bit_for_bit(interpret, group_axis):
    x, dt, a, b, c, d = _scan_inputs(256, 8, 16, 1, n=2)
    if not group_axis:
        b, c = b[:, :, 0], c[:, :, 0]
    args = (x, dt, a, b, c, d)
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    scan = lambda *a: ssd.ssd_scan(*a, chunk=128, interpret=interpret)  # noqa: E731
    ssd.take_scan_records()
    y, stats = scan(*args)
    record, = ssd.take_scan_records()
    g = jax.grad(lambda *a: jnp.sum(w * scan(*a)[0]), tuple(range(6)))(*args)
    got = [float(jnp.sum(y)).hex(), float(y[1, 200, 3, 5]).hex(),
           float(stats.state_sq_sum).hex()] + [float(jnp.sum(v)).hex() for v in g]
    assert got == ONE_GROUP_AT_THE_PARENT[interpret]
    # the parent's record, but for the new fields
    new = {"groups": 1, "group_heads": 8}
    assert {k: v for k, v in record.items() if k not in new} == dict(
        records=2, tokens=256, chunk=128, chunks=2, heads=8, head_dim=16,
        state=16, calls=1, **(dict(kernel=True, heads_per_step=8) if interpret
                              else dict(kernel=False, head_group=8)))
    assert {k: record[k] for k in new} == new


@pytest.mark.parametrize("heads,head_dim,chunk,state,groups,per_step", [
    (64, 64, 128, 128, 8, 8),      # the benchmark's cell: a group a step
    (64, 64, 256, 128, 1, 16),     # one group: what it was
    (64, 64, 128, 128, 16, None),  # 4 heads a group fill no 8 sublanes
    (64, 128, 128, 128, 4, 16),    # wider heads, 16 a group
    (64, 64, 128, 128, 3, None),   # the groups do not divide the heads
])
def test_heads_per_step_stays_inside_a_group(heads, head_dim, chunk, state,
                                             groups, per_step):
    assert ssd_kernel.heads_per_step(
        heads, head_dim, chunk, state, 2, groups) == per_step


def test_a_groups_heads_share_the_live_decay_matrix_budget():
    # 8 of 64 heads fit the budget: one from each of 8 groups, or 8 of the one
    assert ssd.head_group(1, 32, 64, 256) == 8
    assert ssd.head_group(1, 32, 64, 256, 8) == 8
    assert ssd.head_group(1, 32, 64, 256, 2) == 8
    assert ssd.head_group(2, 64, 64, 128, 8) == 8
    # never less than a head a group
    assert ssd.head_group(4, 64, 64, 256, 8) == 8


