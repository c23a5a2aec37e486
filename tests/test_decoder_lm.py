"""The decoder-only language model (nn.DecoderLM, nn.RoutedExperts, the flash
kernel's window and grouped heads) against its plain float32 reference, at
small sizes on the CPU: D 64, 2 periods of 4 layers, 8 query / 2 K/V heads of
16, window 8 at T 32, 8 experts top-2 of width 32, vocabulary 128."""

import importlib.util
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.models import decoder_lm, decoder_lm_reference as ref
from bigdl_tpu.nn.attention import apply_rotary, scaled_dot_product_attention
from bigdl_tpu.nn import moe
from bigdl_tpu.nn.decoder import rope_inv_freq
from bigdl_tpu.nn.moe import buffer_rows
from bigdl_tpu.ops.flash_attention import (
    _VMEM_BUDGET, _dense_reference, _tile_geometry, _window_count,
    _working_set, flash_attention, pick_tiles, take_tile_records)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
PLAIN = {"rope_type": "default", "rope_theta": 500000}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
CONFIG = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=8, layer_types=PERIOD * 2,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    sliding_window=8,
    rope_parameters={"full_attention": YARN, "sliding_attention": PLAIN},
    rms_norm_eps=1e-6, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, norm_topk_prob=True)
N, T = 2, 32


def _tokens(seed):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, CONFIG["vocab_size"], (N, T + 1)).astype(np.int32)
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def _built(config, seed=0):
    model = decoder_lm.from_config(config)
    model.build(jax.random.PRNGKey(seed), jax.ShapeDtypeStruct((N, T), jnp.int32))
    return model, model.get_parameters(), model.get_state()


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


@pytest.fixture(scope="module")
def both():
    """The module's and the reference's loss, logits and gradients on the
    same seeded weights and batch; experts 0-3 of 8 held."""
    config = {**CONFIG, "experts_held": [0, 1, 2, 3]}
    model, params, state = _built(config)
    x, y = _tokens(1)
    criterion = nn.TokenCrossEntropyCriterion()

    def loss(p):
        out, new_state = model.apply(p, state, x, training=True)
        return criterion._apply(out, y), (out, new_state)

    (l, (logits, new_state)), grads = jax.value_and_grad(loss, has_aux=True)(params)
    rcfg = decoder_lm.reference_config(config)
    rparams = decoder_lm.reference_params(params)
    rl, rgrads, counts, _ = ref.loss_and_grad(rparams, x, y, rcfg)
    rlogits = jnp.stack([ref.forward(rparams, x[i], rcfg)[0] for i in range(N)])
    return dict(model=model, loss=l, logits=logits, state=new_state,
                grads=decoder_lm.reference_params(grads), rloss=rl,
                rlogits=rlogits, rgrads=rgrads, counts=counts)


def test_module_loss_and_logits_match_the_reference(both):
    assert float(both["loss"]) == pytest.approx(float(both["rloss"]), abs=1e-5)
    np.testing.assert_allclose(both["logits"], both["rlogits"], atol=5e-6)


LEAVES = ["embed", "final_norm", "head"] + [
    f"layers/{i}/{k}" for i in range(8) for k in (
        "ln1", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "ln2", "router",
        "w_gate", "w_up", "w_down")]


@pytest.mark.parametrize("leaf", LEAVES)
def test_module_gradient_leaf_matches_the_reference(both, leaf):
    got, want = both["grads"], both["rgrads"]
    for key in leaf.split("/"):
        key = int(key) if key.isdigit() else key
        got, want = got[key], want[key]
    assert got.shape == want.shape
    assert _rel(got, want) < 2e-5


def test_counters_match_the_references_own_routing(both):
    got = {k: float(v) for k, v in
           both["model"].counters_tree(both["state"]).items()}
    want = ref.routing_counters(both["counts"])
    assert got["moe_pairs_local"] == want["moe_pairs_local"] > 0
    assert got["moe_load_max_over_mean"] == pytest.approx(
        want["moe_load_max_over_mean"], rel=1e-6)
    assert got["moe_dropped_pairs"] == 0.0


# ------------------------------------------------------------------ the kernel

@pytest.mark.parametrize("t,tile,h,hkv,window", [
    (640, 128, 4, 2, 300), (640, 128, 2, 2, 300), (640, 128, 4, 1, None),
    (4608, None, 2, 1, 2200), (600, None, 2, 1, 300)],
    ids=["window+groups", "window", "groups", "rule-1024x1024", "rule-600x128"])
def test_flash_window_and_grouped_heads_match_dense(t, tile, h, hkv, window):
    """T 640 in tiles of 128: with a window of 300 a k tile 4 below a q tile
    is skipped, the diagonal and the window's edge are masked, the tile one
    below the diagonal is full. At the tiles the rule picks (``tile`` None):
    T 4608 is four and a half tiles of 1024, so under a window of 2200 the
    last q tile is half padding and sees one skipped, two masked and two full
    k tiles, and the last k tile's q-tile stretch is clamped; T 600 is one
    q tile against five k tiles of 128, the last one padded."""
    d = 16
    rng = np.random.default_rng(0)
    q, w = (jnp.asarray(rng.standard_normal((1, h, t, d)), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((1, hkv, t, d)), jnp.float32)
            for _ in range(2))

    def flash(q, k, v):
        return flash_attention(q, k, v, True, block_q=tile, block_k=tile,
                               interpret=True, window=window)

    def dense(q, k, v):
        return _dense_reference(q, k, v, True, None, window)

    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(w * flash(*a)), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(w * dense(*a)), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, atol=2e-5)


@pytest.mark.parametrize("tq,tk,d,itemsize,tiles", [
    (600, 600, 64, 2, (600, 128)),        # the padding bound, as before
    (1536, 1536, 64, 2, (512, 512)),      # 1024 would pad a third on
    (1024, 1024, 64, 2, (1024, 1024)),    # as measured (chip_smoke phase D)
    (2048, 2048, 64, 2, (1024, 1024)),    # as measured (nn.Transformer)
    (4096, 4096, 128, 2, (1024, 1024)),   # as measured (chip_smoke phase D)
    (8192, 8192, 128, 2, (1024, 1024)),   # as measured (Mellum2's layers)
    (8192, 8192, 128, 4, (1024, 1024)),   # float32 operands still fit
    (8192, 8192, 256, 4, (512, 512)),     # over the budget: q halves, then k
    (8192, 8192, 512, 4, (256, 512)),     # and q again
    (1, 4096, 128, 2, (1, 1024)),         # a decode step: one row, whole k tiles
])
def test_tiles_follow_the_shapes(tq, tk, d, itemsize, tiles):
    assert pick_tiles(tq, tk, d, itemsize) == tiles
    bq, bk = tiles
    assert _working_set(bq, bk, d, itemsize) <= _VMEM_BUDGET
    for t, b in ((tq, bq), (tk, bk)):  # _pick_block's bound on padded rows
        assert b <= 128 or ((-t) % b) * 8 <= t
    if d == 256:  # the step before did not fit
        assert _working_set(512, 1024, d, itemsize) > _VMEM_BUDGET


def test_tile_records_say_what_ran_and_how_much_of_it_is_masked():
    # per head: 36 of 64 tile pairs at 1024 x 1024 under the causal mask,
    # 12 % more pairs than the mask lets through; under a window of 1024 a
    # q tile computes two half-masked k tiles: twice the visible pairs
    assert _tile_geometry(8192, 8192, 1024, 1024, True, None) == (
        36, pytest.approx(1.1249, abs=1e-4), pytest.approx(1.1249, abs=1e-4))
    assert _tile_geometry(8192, 8192, 1024, 1024, True, 1024) == (
        15, pytest.approx(2.0, abs=1e-3), pytest.approx(2.0, abs=1e-3))
    assert _tile_geometry(8192, 8192, 512, 512, True, 1024) == (
        45, pytest.approx(1.5, abs=1e-3), pytest.approx(1.5, abs=1e-3))
    assert _tile_geometry(600, 600, 600, 128, False, None) == (
        5, pytest.approx(640 / 600), pytest.approx(640 / 600))
    # sub-blocks of a masked tile: a window of 512 in tiles of 1024 computes
    # 3.87 times the pairs it keeps, 1.5 in sub-blocks of 256; a window of
    # 1024 1.25; the causal diagonal 1.03
    assert _tile_geometry(8192, 8192, 1024, 1024, True, 512, 256) == (
        15, pytest.approx(3.871, abs=1e-3), pytest.approx(1.5, abs=1e-3))
    assert _tile_geometry(8192, 8192, 1024, 1024, True, 1024, 256)[2] == \
        pytest.approx(1.25, abs=1e-3)
    assert _tile_geometry(8192, 8192, 1024, 1024, True, None, 256)[2] == \
        pytest.approx(1.031, abs=1e-3)

    take_tile_records()
    q = jax.ShapeDtypeStruct((1, 2, 2048, 16), jnp.bfloat16)
    attend = lambda **kw: jax.eval_shape(  # noqa: E731
        lambda q: flash_attention(q, q, q, True, **kw), q)
    attend()
    attend()                     # the same shape again: one record
    attend(window=512)
    attend(block_q=256)          # an explicit tile wins, and is what is recorded
    got = {(r["window"], r["block_q"], r["block_k"]): r
           for r in take_tile_records()}
    assert set(got) == {
        (None, 1024, 1024), (512, 1024, 1024), (None, 256, 1024)}
    assert got[(None, 1024, 1024)] == dict(
        tq=2048, tk=2048, d=16, dtype="bfloat16", causal=True, window=None,
        block_q=1024, block_k=1024, visited_tiles=3,
        visited_over_visible=pytest.approx(1.4993, abs=1e-4), sub_block=512,
        computed_over_visible=pytest.approx(1.2494, abs=1e-4),
        backward="fused", backward_acc_bytes=2048 * 32 * 4)
    assert take_tile_records() == []
    attend()                     # a trace older than the caller asks about
    assert take_tile_records(since=time.perf_counter()) == []
    assert take_tile_records() == []  # is dropped, not kept for the next one


def test_compile_record_lists_the_tiles_its_trace_chose():
    from bigdl_tpu.obs.telemetry import Telemetry, observe_jit_compiles

    q = jnp.ones((1, 1, 256, 8), jnp.float32)
    # a trace that no telemetry observed (another shape) must not ride along
    jax.eval_shape(lambda q: flash_attention(q, q, q, True), q[:, :, :128])
    tel = Telemetry()
    for fn in (lambda q: flash_attention(q, q, q, True, interpret=True),
               lambda q: q + 1.0):
        step = jax.jit(fn)
        t0 = time.perf_counter()
        step(q)
        observe_jit_compiles(step, 0, tel, iteration=1,
                             seconds=time.perf_counter() - t0, path="test")
    with_flash, without = [r for r in tel.ring.records
                           if r["type"] == "compile"]
    tel.close()
    assert [(r["tq"], r["block_q"], r["block_k"], r["visited_tiles"])
            for r in with_flash["flash_tiles"]] == [(256, 256, 256, 1)]
    assert "flash_tiles" not in without


def test_window_grid_holds_only_the_tiles_a_window_touches():
    # 8192 keys in tiles of 512, a window of 1024: a q tile of 1024 rows
    # touches 4 k tiles, not 16; a k tile of 512 touches 2 q tiles
    assert _window_count(8, 1024, 512, 0, 0, 1023, 16) == 4
    assert _window_count(16, 512, 1024, 0, 1023, 0, 8) == 2
    assert _window_count(5, 128, 128, 0, 0, 299, 5) == 4
    # and never more than there are
    assert _window_count(2, 128, 128, 0, 0, 4095, 2) == 2


def test_window_needs_causal():
    q = jnp.zeros((1, 2, 128, 16))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, False, interpret=True, window=8)
    with pytest.raises(ValueError, match="causal"):
        scaled_dot_product_attention(q, q, q, window=8)


@pytest.mark.parametrize("window", [None, 8])
def test_dense_attention_path_takes_window_and_grouped_heads(window):
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((2, 8, 32, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 2, 32, 16)), jnp.float32)
            for _ in range(2))
    got = scaled_dot_product_attention(q, k, v, causal=True, mask_q=True,
                                       window=window, impl="dense")
    want = jnp.stack([ref.attention(q[i], k[i], v[i], window, 32)
                      for i in range(2)])
    np.testing.assert_allclose(got, want, atol=2e-6)


# ------------------------------------------------------------------------ RoPE

def test_yarn_inverse_frequencies_and_factor_by_hand():
    inv, factor = rope_inv_freq(YARN, 128)
    assert factor == 1.2772588722239782
    # d(beta) = 128 ln(8192 / (2 pi beta)) / (2 ln 500000)
    d_fast = 128 * math.log(8192 / (2 * math.pi * 32)) / (2 * math.log(500000))
    d_slow = 128 * math.log(8192 / (2 * math.pi * 1)) / (2 * math.log(500000))
    assert (math.floor(d_fast), math.ceil(d_slow)) == (18, 35)
    for i in (0, 18):     # at and below lo: the plain frequency
        assert inv[i] == pytest.approx(500000 ** (-2 * i / 128), rel=1e-6)
    for i in (35, 63):    # at and above hi: divided by 16
        assert inv[i] == pytest.approx(500000 ** (-2 * i / 128) / 16, rel=1e-6)
    i, r = 27, (27 - 18) / (35 - 18)  # between: blended
    base = 500000 ** (-2 * i / 128)
    assert inv[i] == pytest.approx((1 - r) * base + r * base / 16, rel=1e-6)
    plain, one = rope_inv_freq(PLAIN, 128)
    assert one == 1.0
    np.testing.assert_allclose(
        plain, 500000.0 ** (-np.arange(64) / 64), rtol=1e-6)
    # the reference reckons them on its own
    rinv, rfactor = ref.rope_inv_freq(YARN, 128)
    np.testing.assert_allclose(inv, rinv, rtol=1e-5)
    assert rfactor == factor
    # no attention_factor given: 0.1 ln(factor) + 1
    bare = {k: v for k, v in YARN.items() if k != "attention_factor"}
    assert rope_inv_freq(bare, 128)[1] == pytest.approx(0.1 * math.log(16) + 1)


def test_apply_rotary_without_the_new_arguments_is_what_it_was():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 3, 7, 16)),
                    jnp.float32)
    pos = jnp.arange(7) + 5
    half = 8
    freqs = 10000.0 ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    before = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)
    assert jnp.array_equal(apply_rotary(x, pos), before)
    # and with them it is the reference's rotation
    inv, factor = rope_inv_freq(YARN, 16)
    np.testing.assert_allclose(
        apply_rotary(x[0], jnp.arange(7), inv, factor),
        ref.rotate(x[0], jnp.asarray(inv), factor), atol=1e-6)


# --------------------------------------------------------------------- experts

def _experts(held, seed=0, **kw):
    m = nn.RoutedExperts(8, 32, 2, experts_held=held, **kw)
    m.build(jax.random.PRNGKey(seed), jax.ShapeDtypeStruct((N, T, 64), jnp.float32))
    return m


def test_routing_never_drops_under_a_router_biased_onto_one_expert():
    m = _experts((0, 1, 2, 3))
    params = m.get_parameters()
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (N, T, 64))) + 0.1
    # every token's first choice is expert 2: its column alone is large
    params["router"] = params["router"].at[:, 2].set(1.0)
    out, state = m.apply(params, m.get_state(), x)
    counters = state["_counters"]
    want, counts = ref.experts(
        x.reshape(-1, 64), params,
        dict(num_experts_per_tok=2, experts_held=(0, 1, 2, 3)))
    assert int(counts[2]) == N * T             # all 64 tokens chose expert 2
    assert float(counters["moe_dropped_pairs"]) == 0.0
    assert float(counters["moe_pairs_local"]) == float(jnp.sum(counts))
    assert float(counters["moe_load_max_over_mean"]) > 2.0
    np.testing.assert_allclose(out.reshape(-1, 64), want, atol=1e-6)


@pytest.mark.parametrize("n, t", [(N, T), (8, 256)], ids=["one_size", "compact"])
def test_the_four_shares_add_up_to_the_whole_layer(n, t):
    """Experts 0-1, 2-3, 4-5, 6-7 of 8 on four chips: the partial results add
    up to what the uncut reference gives for the whole layer. At 64 tokens
    the buffer has a row for every pair; at 2048 a share's buffer is half
    of them (2048 of 4096 rows) and its ~1024 local pairs fit."""
    N, T = n, t
    assert (buffer_rows(N * T * 2, 2, 8) < N * T * 2) == (n * t > 64)
    whole = _experts(tuple(range(8)))
    params = whole.get_parameters()
    x = jax.random.normal(jax.random.PRNGKey(7), (N, T, 64))
    want, _ = ref.experts(
        x.reshape(-1, 64), params,
        dict(num_experts_per_tok=2, experts_held=tuple(range(8))))
    total, pairs = 0.0, 0.0
    for first in (0, 2, 4, 6):
        held = (first, first + 1)
        share = _experts(held)
        share_params = {
            "router": params["router"],  # whole on every chip
            **{k: params[k][first:first + 2]
               for k in ("w_gate", "w_up", "w_down")}}
        out, state = share.apply(share_params, share.get_state(), x)
        total = total + out
        pairs += float(state["_counters"]["moe_pairs_local"])
        assert float(state["_counters"]["moe_dropped_pairs"]) == 0.0
        assert float(state["_counters"]["moe_overflow_layers"]) == 0.0
    np.testing.assert_allclose(total.reshape(-1, 64), want, atol=1e-6)
    assert pairs == N * T * 2  # every (token, choice) pair on exactly one chip


@pytest.mark.parametrize("pairs, n_held, n_experts, rows", [
    (2 * 8192 * 8, 16, 256, 16384),    # JoyAI-LLM-Flash's cell: an eighth
    (2 * 8192 * 8, 16, 64, 65536),     # Mellum2's cell: a half
    (2 * 8192 * 8, 64, 64, 131072),    # all held: a row for every pair
    (2 * 8192 * 8, 32, 64, 131072),    # a share of a half: the same
    (2 * 8192 * 8, 33, 64, 131072),
    (4096, 1, 8, 1024),                # twice the share, a whole tile of 512
    (3000, 1, 8, 1024),                # 750 rounds up to two tiles
    (3000, 3, 8, 2560),                # 2250 rounds up to five
    (3000, 1, 100, 512),               # 60: one tile
    (128, 2, 8, 128),                  # never more than the pairs
])
def test_the_buffers_rows_follow_from_the_shapes(pairs, n_held, n_experts, rows):
    assert buffer_rows(pairs, n_held, n_experts) == rows
    assert rows == pairs or rows % 512 == 0


def _share_layer(n_held, crowded=False, seed=3):
    """A 16-expert layer's share of ``n_held`` over 1024 tokens (2048 (token,
    choice) pairs), its parameters and a batch; ``crowded``: every token's
    first choice is the held expert 0, so 1024 pairs at least are local."""
    m = nn.RoutedExperts(16, 32, 2, experts_held=tuple(range(n_held)),
                         init_std=0.3)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(seed), (1024, 64))) + 0.1
    m.build(jax.random.PRNGKey(seed + 1), jax.ShapeDtypeStruct(x.shape, x.dtype))
    params = m.get_parameters()
    if crowded:
        params["router"] = params["router"].at[:, 0].set(1.0)
    return m, params, x


def _out_and_grads(m, params, x, wrap=lambda m: m):
    """The layer's output, its counters, and the gradients of a seeded
    projection of the output by the four parameter tensors and the input."""
    cot = jax.random.normal(jax.random.PRNGKey(11), x.shape)
    layer = wrap(m)
    boxed = layer is not m

    def f(params, x):
        out, state = layer.apply({m.name(): params} if boxed else params,
                                 {m.name(): m.get_state()} if boxed
                                 else m.get_state(), x)
        return jnp.sum(out * cot), (out, state[m.name()] if boxed else state)

    (_, (out, state)), grads = jax.value_and_grad(
        f, argnums=(0, 1), has_aux=True)(params, x)
    return out, state["_counters"], grads


# 1 and 2 of 16 held: a buffer of 512 of the 2048 rows; 4 of 16: 1024 rows.
# Crowded, the local pairs take two to four blocks of the buffer
@pytest.mark.parametrize("wrap", [lambda m: m, nn.Remat], ids=["bare", "remat"])
@pytest.mark.parametrize("crowded", [False, True], ids=["fits", "overflows"])
@pytest.mark.parametrize("n_held", [1, 2, 4])
def test_the_sized_buffer_gives_what_a_row_for_every_pair_gives(
        n_held, crowded, wrap, monkeypatch):
    m, params, x = _share_layer(n_held, crowded)
    c = buffer_rows(2048, n_held, 16)
    assert c < 2048
    out, counters, grads = _out_and_grads(m, params, x, wrap)
    local = float(counters["moe_pairs_local"])
    assert (1024 <= local <= 2048 and local > c) if crowded else 0 < local <= c
    assert float(counters["moe_overflow_layers"]) == float(crowded)
    assert float(counters["moe_dropped_pairs"]) == 0.0
    # with a slack of 16 these buffers have a row for every pair: the
    # one-size computation the layer was before its buffer shrank
    monkeypatch.setattr(moe, "_SHARE_SLACK", 16)
    want_out, want_counters, want_grads = _out_and_grads(m, params, x)
    assert float(want_counters["moe_overflow_layers"]) == 0.0
    assert float(want_counters["moe_pairs_local"]) == local
    # the same products; a token's rows (and, block by block, an expert's)
    # are summed in another order: float32 reassociation
    leaves = jax.tree_util.tree_leaves_with_path((out, grads))
    assert len(leaves) == 6   # output; router, the three expert tensors; input
    for (path, got), want in zip(
            leaves, jax.tree_util.tree_leaves((want_out, want_grads))):
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0, path
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * scale,
                                   err_msg=str(path))


def _primitives(jaxpr, found=None):
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


@pytest.mark.parametrize("n_held, looped", [(16, False), (8, False), (2, True)])
def test_a_layer_that_holds_half_or_more_has_one_pass(n_held, looped):
    """No loop over blocks, no conditional and no rule of its own in the
    program of a layer whose buffer has a row for every pair."""
    m, params, x = _share_layer(n_held)
    for f in (lambda p, x: m.apply(p, m.get_state(), x)[0],
              jax.grad(lambda p, x: jnp.sum(m.apply(p, m.get_state(), x)[0]))):
        found = _primitives(jax.make_jaxpr(f)(params, x).jaxpr)
        assert ("while" in found) == looped
        assert "cond" not in found


def test_experts_held_must_be_distinct_ids():
    with pytest.raises(ValueError, match="experts_held"):
        nn.RoutedExperts(8, 32, 2, experts_held=(1, 1))
    with pytest.raises(ValueError, match="experts_held"):
        nn.RoutedExperts(8, 32, 2, experts_held=(8,))
    with pytest.raises(ValueError, match="top_k"):
        nn.RoutedExperts(8, 32, 9)


def test_counters_tree_sums_and_takes_the_worst():
    state = {"a": {"_counters": {"pairs": jnp.float32(3), "load_max": jnp.float32(2)}},
             "b": {"c": {"_counters": {"pairs": jnp.float32(4), "load_max": jnp.float32(5)}}},
             "d": {"running_mean": jnp.zeros(3)}}
    got = nn.Identity().counters_tree(state)
    assert {k: float(v) for k, v in got.items()} == {"pairs": 7.0, "load_max": 5.0}
    assert nn.Identity().counters_tree({"x": {}}) == {}


# ------------------------------------------------------------ criterion, copies

def test_token_cross_entropy_is_the_mean_negative_log_likelihood():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.standard_normal((2, 5, 11)) * 3, jnp.float32)
    target = jnp.asarray(rng.integers(0, 11, (2, 5)), jnp.int32)
    criterion = nn.TokenCrossEntropyCriterion()

    def plain(z):
        logp = jax.nn.log_softmax(z, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, target[..., None], -1))

    assert float(criterion.forward(logits, target)) == pytest.approx(
        float(plain(logits)), rel=1e-6)
    np.testing.assert_allclose(criterion.backward(logits, target),
                               jax.grad(plain)(logits), atol=1e-7)


@pytest.mark.parametrize("operands", [None, "bfloat16"])
def test_reference_product_rounds_operands_and_cotangent(operands):
    """``product`` is einsum; at a stated operand precision it rounds both
    operands first, and the cotangent and the other operand in the two
    products of its gradient, and still sums in float32."""
    rng = np.random.default_rng(0)
    a, b, g = (jnp.asarray(rng.standard_normal(s), jnp.float32)
               for s in ((5, 7), (7, 3), (5, 3)))
    cut = (lambda v: v) if operands is None else (
        lambda v: v.astype(operands).astype(jnp.float32))
    out, back = jax.vjp(lambda a, b: ref.product("ik,kj->ij", a, b, operands),
                        a, b)
    assert out.dtype == jnp.float32
    np.testing.assert_array_equal(out, jnp.einsum("ik,kj->ij", cut(a), cut(b)))
    da, db = back(g)
    np.testing.assert_array_equal(da, jnp.einsum("ij,kj->ik", cut(g), cut(b)))
    np.testing.assert_array_equal(db, jnp.einsum("ik,ij->kj", cut(a), cut(g)))


def test_reference_at_bfloat16_operands_leaves_the_router_in_float32():
    """The stated precision rounds the products' operands, never the router:
    on the same weights the routing is the float32 reference's own, and the
    logits differ by the operand rounding."""
    config = {**CONFIG, "num_hidden_layers": 1, "experts_held": [0, 1, 2, 3]}
    _, params, _ = _built(config, seed=5)
    x, _ = _tokens(6)
    rcfg = decoder_lm.reference_config(config)
    rparams = decoder_lm.reference_params(params)
    lp = rparams["layers"][0]
    h = jnp.asarray(np.random.default_rng(7).standard_normal((T, 64)),
                    jnp.float32)
    plain, counts = ref.experts(h, lp, rcfg)
    rounded, rcounts = ref.experts(h, lp, {**rcfg, "operands": "bfloat16"})
    assert jnp.array_equal(counts, rcounts)
    assert 1e-4 < _rel(rounded, plain) < 2e-2


@pytest.mark.parametrize("operands", [None, "bfloat16"])
def test_the_benchmarks_copy_of_the_reference_gives_identical_outputs(operands):
    path = os.path.join(ROOT, "benchmark", "configs", "mellum2_12b_reference.py")
    spec = importlib.util.spec_from_file_location("bench_reference_copy", path)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    config = {**CONFIG, "num_hidden_layers": 4, "experts_held": [2, 3, 4, 5]}
    _, params, _ = _built(config, seed=3)
    x, y = _tokens(4)
    rcfg = {**decoder_lm.reference_config(config), "operands": operands}
    rparams = decoder_lm.reference_params(params)
    at = jnp.asarray([[0, 5, 31], [7, 7, 30]])
    a = ref.loss_and_grad(rparams, x, y, rcfg, at=at)
    b = copy.loss_and_grad(rparams, x, y, rcfg, at=at)
    assert a[3].shape == (N, 3, CONFIG["vocab_size"])
    for u, v in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert jnp.array_equal(u, v)
    assert jnp.array_equal(ref.forward(rparams, x[0], rcfg)[0],
                           copy.forward(rparams, x[0], rcfg)[0])


# ------------------------------------------------------------- through optimize

class _Keep:
    def __init__(self):
        self.records = []

    def emit(self, record):
        self.records.append(record)

    def flush(self):
        pass

    def close(self):
        pass


def test_language_model_trains_through_optimize_with_counters_in_the_record():
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.obs import Telemetry
    from bigdl_tpu.optim import Adam, LocalOptimizer
    from bigdl_tpu.optim.trigger import Trigger

    config = {**CONFIG, "num_hidden_layers": 4, "experts_held": [0, 1, 2, 3]}
    p = 1.0 / np.arange(1, 129)
    tok = np.random.default_rng(0).choice(
        128, size=(16, T + 1), p=p / p.sum()).astype(np.int32)
    data = DataSet.array(tok[:, :-1].copy(), tok[:, 1:].copy(), batch_size=2)
    opt = LocalOptimizer(decoder_lm.from_config(config), data,
                         nn.TokenCrossEntropyCriterion())
    opt.set_optim_method(Adam(learningrate=3e-3, beta1=0.9, beta2=0.95))
    keep = _Keep()
    tel = Telemetry(exporters=[keep])
    opt.set_telemetry(tel)
    opt.set_end_when(Trigger.max_iteration(24))
    opt.optimize()
    tel.close()
    steps = [r for r in keep.records if r.get("type") == "step"]
    assert len(steps) == 24
    assert steps[0]["loss"] == pytest.approx(math.log(128), abs=0.5)
    assert np.median([r["loss"] for r in steps[-8:]]) < np.median(
        [r["loss"] for r in steps[:4]])
    for r in steps:
        assert r["moe_dropped_pairs"] == 0.0
        assert 0 < r["moe_pairs_local"] <= 4 * N * T * 2
        assert r["moe_load_max_over_mean"] >= 1.0
    assert steps[-1]["compile_count"] == 1
    assert sum(r["count"] for r in keep.records
               if r.get("type") == "compile") == 1
