"""The latent-attention, sparse-expert language model with a multi-token-
prediction module (nn.LatentAttention, nn.RoutedExperts' sigmoid router with
its selection bias and shared expert, nn.MultiTokenPredictor, the flash kernel
at unequal q/k and v head sizes) against its plain float32 reference, at
small sizes on the CPU: D 32, 1 dense + 2 sparse layers + the MTP module, 4
heads of 8 + 4 (q/k) and 6 (v), 8 experts top-2 of width 12, vocabulary 96."""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.models import decoder_lm, latent_moe_lm_reference as ref
from bigdl_tpu.nn.attention import apply_rotary, scaled_dot_product_attention
from bigdl_tpu.nn.moe import (route_sigmoid_top_k, route_top_k,
                              selection_bias_update)
from bigdl_tpu.ops.flash_attention import (
    _VMEM_BUDGET, _dense_reference, _working_set, flash_attention, pick_tiles,
    take_tile_records)
from bigdl_tpu.utils.table import Table

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=6, rope_theta=10000.0, rope_scaling=None,
    rope_interleave=True, first_k_dense_replace=1, moe_layer_freq=1,
    intermediate_size=48, moe_intermediate_size=12, n_routed_experts=8,
    n_shared_experts=1, num_experts_per_tok=2, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=1, topk_group=1, norm_topk_prob=True,
    routed_scaling_factor=2.5, num_nextn_predict_layers=1, rms_norm_eps=1e-6,
    tie_word_embeddings=False, initializer_range=0.125,
    router_bias_update_rate=0.001)
N, T, WEIGHT = 2, 16, 0.1


def _tokens(seed):
    tok = np.random.default_rng(seed).integers(
        0, CONFIG["vocab_size"], (N, T + 1)).astype(np.int32)
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def _with_biases(state, seed=5, size=0.05):
    """``state`` with every router's selection bias drawn from N(0, size):
    at zero, choice by s + b and choice by s are one."""
    def draw(path, leaf):
        name = jax.tree_util.keystr(path)
        if "selection_bias" not in name:
            return leaf
        return size * jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(seed), len(name)), leaf.shape)
    return jax.tree_util.tree_map_with_path(draw, state)


def _built(config, seed=0):
    model = decoder_lm.from_config(config)
    model.build(jax.random.PRNGKey(seed), jax.ShapeDtypeStruct((N, T), jnp.int32))
    return model, model.get_parameters(), _with_biases(model.get_state())


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _reference_config(config, **changed):
    return {**decoder_lm.reference_config(config), "mtp_loss_weight": WEIGHT,
            **changed}


@pytest.fixture(scope="module")
def both():
    """The module's and the reference's loss, both heads' logits, gradients,
    counters and biases after the step on the same seeded weights, biases and
    batch; experts 0, 1, 2 and 5 of 8 held."""
    config = {**CONFIG, "experts_held": [0, 1, 2, 5]}
    model, params, state = _built(config)
    x, y = _tokens(1)
    criterion = nn.MultiTokenCrossEntropyCriterion(WEIGHT)

    def loss(p):
        out, new_state = model.apply(p, state, x, training=True)
        l, counted = criterion.counted(out, y)
        return l, (out, model.with_counters(new_state, counted))

    (l, (out, new_state)), grads = jax.value_and_grad(loss, has_aux=True)(params)
    rcfg = _reference_config(config)
    rparams = decoder_lm.reference_params(params)
    rbiases = decoder_lm.reference_biases(state)
    at = jnp.tile(jnp.arange(T), (N, 1))
    rl, rgrads, stats, picked = ref.loss_and_grad(
        rparams, rbiases, x, y, rcfg, at=at)
    return dict(model=model, config=config, params=params, state=state,
                x=x, y=y, loss=l, logits=out[1], logits_1=out[2],
                new_state=new_state, grads=decoder_lm.reference_params(grads),
                rcfg=rcfg, rparams=rparams, rbiases=rbiases, rloss=rl,
                rgrads=rgrads, stats=stats, rlogits=picked[:, 0],
                rlogits_1=picked[:, 1])


def test_from_config_builds_the_one_decoder_class(both):
    model = both["model"]
    assert type(model) is nn.DecoderLM
    blocks = [m.modules[0] for m in model.modules if isinstance(m, nn.Remat)]
    assert [type(b.modules[1]) for b in blocks] == [nn.LatentAttention] * 3
    assert [type(b.modules[3]).__name__ for b in blocks] == [
        "GatedMLP", "RoutedExperts", "RoutedExperts"]
    assert isinstance(model.modules[-1], nn.MultiTokenPredictor)
    mtp_block = model.modules[-1].modules[3].modules[0]
    assert type(mtp_block.modules[3]).__name__ == "RoutedExperts"
    assert sorted(both["params"]["mtp"]) == [
        "eh_proj", "enorm", "hnorm", "layer", "norm"]
    assert both["params"]["mtp"]["eh_proj"]["weight"].shape == (64, 32)


def test_module_loss_and_both_logits_match_the_reference(both):
    assert float(both["loss"]) == pytest.approx(float(both["rloss"]), abs=1e-5)
    np.testing.assert_allclose(both["logits"], both["rlogits"], atol=1e-5)
    np.testing.assert_allclose(both["logits_1"], both["rlogits_1"], atol=1e-5)
    counters = both["model"].counters_tree(both["new_state"])
    assert float(counters["mtp_loss"]) == pytest.approx(
        float(both["stats"]["mtp_loss"]), abs=1e-5)
    assert float(both["loss"]) == pytest.approx(
        float(both["stats"]["main_loss"])
        + WEIGHT * float(both["stats"]["mtp_loss"]), abs=1e-5)


_ATTENTION = ("ln1", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b",
              "wo", "ln2")
_SPARSE = ("router", "w_gate", "w_up", "w_down", "shared_in", "shared_out")
LEAVES = ["embed", "final_norm", "head"] + [
    f"layers/0/{k}" for k in _ATTENTION + ("w_in", "w_out")] + [
    f"layers/{i}/{k}" for i in (1, 2) for k in _ATTENTION + _SPARSE] + [
    f"mtp/{k}" for k in ("enorm", "hnorm", "eh_proj", "norm")] + [
    f"mtp/layer/{k}" for k in _ATTENTION + _SPARSE]


@pytest.mark.parametrize("leaf", LEAVES)
def test_module_gradient_leaf_matches_the_reference(both, leaf):
    got, want = both["grads"], both["rgrads"]
    for key in leaf.split("/"):
        key = int(key) if key.isdigit() else key
        got, want = got[key], want[key]
    assert got.shape == want.shape
    assert float(jnp.linalg.norm(want)) > 0
    assert _rel(got, want) < 3e-5


def test_every_gradient_leaf_is_compared(both):
    paths = {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
             for p, _ in jax.tree_util.tree_leaves_with_path(both["rgrads"])}
    assert paths == set(LEAVES)


def test_counters_and_the_bias_after_the_step_match_the_reference(both):
    got = {k: float(v) for k, v in
           both["model"].counters_tree(both["new_state"]).items()}
    want = ref.routing_counters(both["stats"], both["rcfg"])
    assert set(want) == {
        "moe_pairs_local", "moe_load_max_over_mean", "moe_dropped_pairs",
        "moe_bias_abs_max", "mtp_loss"}
    # and one of the program's own, about its buffer: the reference has none
    assert set(got) == set(want) | {"moe_overflow_layers"}
    assert got["moe_overflow_layers"] == 0.0
    assert got["moe_pairs_local"] == want["moe_pairs_local"] > 0
    assert got["moe_load_max_over_mean"] == pytest.approx(
        want["moe_load_max_over_mean"], rel=1e-6)
    assert got["moe_dropped_pairs"] == 0.0
    assert got["moe_bias_abs_max"] == pytest.approx(want["moe_bias_abs_max"])
    after = decoder_lm.reference_biases(both["new_state"])
    assert len(after) == len(both["stats"]["biases"]) == 3   # 2 layers + MTP
    for b, rb, before in zip(after, both["stats"]["biases"], both["rbiases"]):
        np.testing.assert_array_equal(b, rb)
        moved = np.abs(np.asarray(b - before))
        assert set(np.round(moved / 0.001, 3)) <= {0.0, 1.0} and moved.max() > 0
    # every pair of every token is counted, over all 8 experts
    np.testing.assert_array_equal(
        jnp.sum(both["stats"]["counts"], axis=-1), [N * T * 2] * 3)


def test_the_bias_is_state_with_no_gradient_and_no_optimizer_slot(both):
    from bigdl_tpu.optim import Adam

    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(both["params"])]
    assert not any("bias" in n for n in names)
    slots = Adam().init_slots(both["params"])
    assert not any("bias" in jax.tree_util.keystr(p) for p, _ in
                   jax.tree_util.tree_leaves_with_path(slots))
    model, params, x, y = (both[k] for k in ("model", "params", "x", "y"))

    def loss(state):
        out, _ = model.apply(params, state, x, training=True)
        return nn.MultiTokenCrossEntropyCriterion(WEIGHT)._apply(out, y)

    g = jax.grad(loss, allow_int=True)(both["state"])
    for b in decoder_lm.reference_biases(g):
        assert float(jnp.max(jnp.abs(b))) == 0.0
    # an evaluation forward leaves the bias where it was
    _, held = model.apply(params, both["state"], x, training=False)
    for b, before in zip(decoder_lm.reference_biases(held), both["rbiases"]):
        np.testing.assert_array_equal(b, before)


# ------------------------------------------------------------------ the kernel

@pytest.mark.parametrize("d,d_v,h,hkv,tile", [
    (192, 128, 2, 2, 128), (96, 64, 4, 2, 128), (48, 80, 2, 1, None),
    (64, 64, 2, 2, 128)], ids=["192/128", "96/64+groups", "48/80", "64/64"])
def test_flash_at_unequal_head_sizes_matches_dense(d, d_v, h, hkv, tile):
    """q and k heads of one size, v and output heads of another, a size that
    is no multiple of the 128 lanes among them: values and dQ, dK, dV against
    dense attention, T 320 in tiles of 128 (full, diagonal and padded tiles)."""
    t = 320
    ks = jax.random.split(jax.random.PRNGKey(d), 4)
    q = jax.random.normal(ks[0], (1, h, t, d))
    k = jax.random.normal(ks[1], (1, hkv, t, d))
    v = jax.random.normal(ks[2], (1, hkv, t, d_v))
    cot = jax.random.normal(ks[3], (1, h, t, d_v))
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, True, interpret=True, block_q=tile, block_k=tile), q, k, v)
    want, rvjp = jax.vjp(lambda q, k, v: _dense_reference(
        q, k, v, True, None), q, k, v)
    assert out.shape == (1, h, t, d_v)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref_, like in zip(vjp(cot), rvjp(cot), (q, k, v)):
        assert got.shape == like.shape
        np.testing.assert_allclose(got, ref_, atol=5e-5)


def test_flash_at_equal_head_sizes_is_what_it_was():
    """The same tiles, the same record (no ``d_v`` key) and the same working
    set as before the kernels took a second head size."""
    assert pick_tiles(8192, 8192, 128, 2) == pick_tiles(8192, 8192, 128, 2, 128) \
        == (1024, 1024)
    assert pick_tiles(8192, 8192, 64, 2) == (1024, 1024)
    for d in (64, 128):
        assert _working_set(1024, 1024, d, 2) == _working_set(1024, 1024, d, 2, d)
    assert _working_set(1024, 1024, 128, 2) == 2 * 1024 * 1024 * 4 \
        + 2 * (2 + 4) * 1024 * 128 * 2 + 2 * 1024 * 128 * 4
    take_tile_records()
    q = jnp.ones((1, 2, 256, 16))
    flash_attention(q, q, q, True, interpret=True)
    record, = take_tile_records()
    assert "d_v" not in record and record["d"] == 16
    flash_attention(q, q, q[..., :8], True, interpret=True)
    record, = take_tile_records()
    assert record["d"] == 16 and record["d_v"] == 8


def test_tiles_at_the_latent_head_sizes_fit_the_budget():
    bq, bk = pick_tiles(8192, 8192, 192, 2, 128)
    assert (bq, bk) == (1024, 1024)
    assert _working_set(bq, bk, 192, 2, 128) <= _VMEM_BUDGET
    # float32 operands at the same sizes do not, and the q tile halves first
    assert pick_tiles(8192, 8192, 192, 4, 128) == (512, 1024)


def test_q_and_k_must_share_a_head_size():
    q = jnp.ones((1, 2, 128, 16))
    with pytest.raises(ValueError, match="share a head size"):
        flash_attention(q, q[..., :8], q, True, interpret=True)


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


@pytest.mark.parametrize("n,h,hkv,t,d,d_v,window,calls", [
    (2, 32, 32, 8192, 192, 128, None, 2),    # JoyAI's blocks
    (2, 32, 4, 8192, 128, 128, None, 2),     # Mellum2's full layer
    (2, 32, 4, 8192, 128, 128, 1024, 2),     # and its sliding layers
    (1, 32, 8, 8192, 64, 64, None, 2),       # granite's attention layer
    (1, 2, 2, 32768, 128, 128, None, 3),     # too long a key axis: the pair
    (1, 48, 8, 8192, 128, 128, None, 2),     # Laguna's full layers: 6 a K/V head
    (1, 72, 8, 8192, 128, 128, 512, 2),      # its sliding layers: 9 a K/V head
], ids=["joyai", "mellum-full", "mellum-window", "granite", "T32768",
        "laguna-full", "laguna-window"])
def test_the_kernels_compile_at_the_cells_shapes_for_a_v5e(
        one_chip, n, h, hkv, t, d, d_v, window, calls):
    """Interpret mode passes what Mosaic refuses: q/k heads of 192 (one and a
    half lane groups) with v heads of 128, grouped heads, a window, head size
    64, each at the cell's real sizes and the tiles the rule picks: the
    forward kernel and the one backward kernel with its whole-axis dK/dV
    accumulator under the VMEM limit it asks for; a key axis of 32768 gets
    the pair."""
    from jax.experimental.compilation_cache import compilation_cache

    shape = lambda heads, size: jax.ShapeDtypeStruct(  # noqa: E731
        (n, heads, t, size), jnp.bfloat16, sharding=one_chip)

    def fwd_bwd(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, True, window=window), q, k, v)
        return out, vjp(g)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(fwd_bwd).lower(
            shape(h, d), shape(hkv, d), shape(hkv, d_v),
            shape(h, d_v)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert text.count("tpu_custom_call") == calls


def test_the_sized_buffers_loop_compiles_at_the_cells_shapes_for_a_v5e(
        one_chip, monkeypatch):
    """The routed layer of the cell (16,384 tokens of 2048, 16 of 256 experts
    of 768 held, 8 a token) forward and backward for the described chip: the
    grouped kernels at the buffer's 16,384 rows inside the two block loops
    (12 calls: 3 forward, 3 + 6 backward, one set at one shape), no
    conditional, and no temporary of the 131,072 pairs' size."""
    from jax.experimental.compilation_cache import compilation_cache

    from bigdl_tpu.nn import moe
    from bigdl_tpu.utils.engine import Engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # megablox
    monkeypatch.setattr(Engine._state, "compute_dtype", "bfloat16")
    t, d, f, e, held, k = 16384, 2048, 768, 256, 16, 8
    assert moe.buffer_rows(t * k, held, e) == 16384
    shape = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.float32, sharding=one_chip)
    params = {"router": shape(d, e), "w_gate": shape(held, d, f),
              "w_up": shape(held, d, f), "w_down": shape(held, f, d)}

    def fwd_bwd(params, x, g):
        out, vjp = jax.vjp(lambda p, x: moe.routed_experts(
            x, p, n_experts=e, experts_held=tuple(range(held)), top_k=k)[0],
            params, x)
        return out, vjp(g)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(fwd_bwd).lower(
            params, shape(t, d), shape(t, d)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 12
    assert " while(" in text and " conditional(" not in text
    # a row for every pair held 2 float32 buffers of 131072 x 2048 at least
    assert compiled.memory_analysis().temp_size_in_bytes < 131072 * 2048 * 4


def test_the_routed_layer_compiles_at_the_gated_models_widths_for_a_v5e(
        one_chip, monkeypatch):
    """Laguna-S-2.1's routed layer (8192 tokens of 3072, 8 of 256 experts of
    1024 held, 10 a token) forward and backward for the described chip: the
    transposed grouped product at a (1024, 3072) tile pair asked 17 MiB of
    the 16 MiB of scoped VMEM a kernel may have; with the columns' tile cut
    to 768 (``_transposed_tiling``) the 12 calls compile."""
    from jax.experimental.compilation_cache import compilation_cache

    from bigdl_tpu.nn import moe
    from bigdl_tpu.utils.engine import Engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # megablox
    monkeypatch.setattr(Engine._state, "compute_dtype", "bfloat16")
    t, d, f, e, held, k = 8192, 3072, 1024, 256, 8, 10
    assert moe.buffer_rows(t * k, held, e) == 5120
    assert moe._transposed_tiling(f, d) == (512, 1024, 768)
    shape = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.float32, sharding=one_chip)
    params = {"router": shape(d, e), "w_gate": shape(held, d, f),
              "w_up": shape(held, d, f), "w_down": shape(held, f, d)}

    def fwd_bwd(params, x, g):
        out, vjp = jax.vjp(lambda p, x: moe.routed_experts(
            x, p, n_experts=e, experts_held=tuple(range(held)), top_k=k)[0],
            params, x)
        return out, vjp(g)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(fwd_bwd).lower(
            params, shape(t, d), shape(t, d)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert compiled.as_text().count("tpu_custom_call") == 12


# ------------------------------------------------------------- latent attention

def test_interleaved_rotary_by_hand():
    x = jnp.arange(2 * 3 * 4, dtype=jnp.float32).reshape(2, 3, 4) + 1.0
    inv_freq = jnp.asarray([1.0, 0.25])
    got = apply_rotary(x, jnp.arange(3), inv_freq, interleaved=True)
    for pos in range(3):
        for i, f in enumerate((1.0, 0.25)):
            a, b = x[:, pos, 2 * i], x[:, pos, 2 * i + 1]
            c, s = math.cos(pos * f), math.sin(pos * f)
            np.testing.assert_allclose(got[:, pos, 2 * i], a * c - b * s, rtol=1e-6)
            np.testing.assert_allclose(got[:, pos, 2 * i + 1], a * s + b * c,
                                       rtol=1e-6)
    # the half-split form pairs (i, i + d/2): another rotation of the same x
    assert not np.allclose(got, apply_rotary(x, jnp.arange(3), inv_freq))
    # and the reference's own rotation: theta 16 over 4 dims is (1, 1/4)
    np.testing.assert_allclose(got, ref.rotate(x, 16.0, True), rtol=1e-6)


def test_latent_attention_by_hand_at_one_position():
    """Position 2 of one record: the low-rank paths, the shared rotary k head
    and the interleaved pairs written out with numpy."""
    attn = nn.LatentAttention(2, q_rank=6, kv_rank=5, nope_dim=4, rope_dim=2,
                              v_dim=3, rope={"rope_theta": 100.0},
                              interleaved=True, init_std=0.5)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 8))
    attn.build(jax.random.PRNGKey(1), jax.ShapeDtypeStruct(x.shape, x.dtype))
    p = {k: np.asarray(v, np.float64) for k, v in attn.get_parameters().items()}
    p["q_norm"] = p["q_norm"] * 1.5   # gains that are not one
    p["kv_norm"] = p["kv_norm"] * 0.5
    got, _ = attn.apply({k: jnp.asarray(v, jnp.float32) for k, v in p.items()},
                        {}, x)
    xs = np.asarray(x[0], np.float64)
    norm = lambda a, g: a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-6) * g  # noqa: E731

    def turn(pair, pos):     # rope_dim 2: one pair, frequency 100^0 = 1
        c, s = math.cos(pos), math.sin(pos)
        return np.array([pair[0] * c - pair[1] * s, pair[0] * s + pair[1] * c])

    q = (norm(xs @ p["wq_a"], p["q_norm"]) @ p["wq_b"]).reshape(3, 2, 6)
    kv_a = xs @ p["wkv_a"]
    kv = (norm(kv_a[:, :5], p["kv_norm"]) @ p["wkv_b"]).reshape(3, 2, 7)
    k_rope = np.stack([turn(kv_a[t, 5:], t) for t in range(3)])   # ONE head
    ctx = []
    for h in range(2):
        qh = np.concatenate([q[2, h, :4], turn(q[2, h, 4:], 2)])
        scores = np.array([
            qh @ np.concatenate([kv[t, h, :4], k_rope[t]]) for t in range(3)
        ]) / math.sqrt(6)
        w = np.exp(scores - scores.max())
        ctx.append((w / w.sum()) @ kv[:, h, 4:])
    want = np.concatenate(ctx) @ p["wo"]
    np.testing.assert_allclose(got[0, 2], want, rtol=2e-5, atol=2e-6)


def test_dense_attention_path_takes_unequal_head_sizes():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 2, 8, 6))
    k = jax.random.normal(ks[1], (1, 2, 8, 6))
    v = jax.random.normal(ks[2], (1, 2, 8, 3))
    got = scaled_dot_product_attention(q, k, v, causal=True, impl="dense")
    np.testing.assert_allclose(got, _dense_reference(q, k, v, True, None),
                               atol=1e-6)


# --------------------------------------------------------------------- the router

def test_sigmoid_router_by_hand():
    """Choice by s + b, weights by s, the 1e-20 and the 2.5."""
    x = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [0.0, 0.0]])
    w = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [-30.0, -30.0, -30.0, -30.0]])
    b = jnp.asarray([0.0, 0.0, 0.5, 0.0])
    weights, ids = route_sigmoid_top_k(x, w, b, 2, 2.5)
    s = 1 / (1 + np.exp(-np.asarray([2.0, 1.0, 0.0, -1.0])))
    # token 0: s + b = .88, .73, 1.0, .27: experts 2 and 0, weighted by s
    assert sorted(np.asarray(ids[0])) == [0, 2]
    order = np.asarray(ids[0])
    np.testing.assert_allclose(
        weights[0], 2.5 * s[order] / (s[0] + s[2] + 1e-20), rtol=1e-6)
    # without the bias the choice is by s alone
    _, plain = route_sigmoid_top_k(x, w, None, 2, 2.5)
    assert sorted(np.asarray(plain[0])) == [0, 1]
    # token 1: every score is sigmoid(-30) = 9e-14: the 1e-20 changes nothing
    # one can see, and the weights are 2.5 / 2 each
    np.testing.assert_allclose(weights[1], [1.25, 1.25], rtol=1e-5)
    # scores that underflow to zero divide by the 1e-20, not by zero
    zero, _ = route_sigmoid_top_k(jnp.asarray([[1.0, 0.0]]),
                                  jnp.asarray([[-200.0] * 4, [0.0] * 4]),
                                  None, 2, 2.5)
    assert np.all(np.isfinite(zero)) and float(jnp.max(zero)) == 0.0
    # the gradient reaches the router through s, never through b
    g = jax.grad(lambda b: jnp.sum(route_sigmoid_top_k(x, w, b, 2, 2.5)[0]))(b)
    assert float(jnp.max(jnp.abs(g))) == 0.0


def test_bias_update_by_hand():
    top_e = jnp.asarray([[0, 1], [0, 2], [0, 1]])     # counts 3, 2, 1, 0
    b = jnp.asarray([0.1, 0.0, -0.2, 0.0])
    got = selection_bias_update(b, top_e, 0.01)
    # mean 1.5: experts 0 and 1 over it, 2 and 3 under it
    np.testing.assert_allclose(got, [0.09, -0.01, -0.19, 0.01], atol=1e-7)
    even = selection_bias_update(b, jnp.asarray([[0, 1], [2, 3]]), 0.01)
    np.testing.assert_array_equal(even, b)            # at the mean: sign 0


def test_softmax_router_is_what_it_was():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    p, e = route_top_k(x, w, 2)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(x @ w, axis=-1), 2)
    np.testing.assert_array_equal(e, top_e)
    np.testing.assert_allclose(p, top_p / top_p.sum(-1, keepdims=True), rtol=1e-6)
    layer = nn.RoutedExperts(6, 4, 2)
    layer.build(jax.random.PRNGKey(2), jax.ShapeDtypeStruct((5, 8), jnp.float32))
    assert sorted(layer.get_parameters()) == ["router", "w_down", "w_gate", "w_up"]
    assert sorted(layer.get_state()["_counters"]) == [
        "moe_dropped_pairs", "moe_load_max_over_mean", "moe_overflow_layers",
        "moe_pairs_local"]
    with pytest.raises(ValueError, match="sigmoid"):
        nn.RoutedExperts(6, 4, 2, bias_update_rate=0.001)
    with pytest.raises(ValueError, match="one of"):
        nn.RoutedExperts(6, 4, 2, scoring="tanh")


def test_the_shares_and_the_shared_expert_once_add_up_to_the_whole_layer():
    """The four shares' routed parts plus the shared expert counted once give
    what the uncut reference's layer gives."""
    kw = dict(scoring="sigmoid", routed_scaling=2.5, bias_update_rate=0.001,
              shared_size=12, init_std=0.3)
    whole = nn.RoutedExperts(8, 12, 2, **kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (N * T, 32))
    whole.build(jax.random.PRNGKey(1), jax.ShapeDtypeStruct(x.shape, x.dtype))
    params = whole.get_parameters()
    state = {**whole.get_state(),
             "selection_bias": 0.1 * jax.random.normal(jax.random.PRNGKey(2), (8,))}
    rcfg = dict(num_experts_per_tok=2, routed_scaling_factor=2.5,
                experts_held=list(range(8)))
    want, counts = ref.experts(x, params, state["selection_bias"], rcfg)
    got, new_state = whole.apply(params, state, x, training=True)
    np.testing.assert_allclose(got, want, atol=2e-5)
    shared = ref.gated_mlp(x, params["shared_in"], params["shared_out"])
    total, local = shared, 0.0
    for held in ([0, 1], [2, 3], [4, 5], [6, 7]):
        share = nn.RoutedExperts(8, 12, 2, experts_held=held, **kw)
        share.build(jax.random.PRNGKey(1), jax.ShapeDtypeStruct(x.shape, x.dtype))
        idx = jnp.asarray(held)
        p = {**params, **{k: params[k][idx] for k in ("w_gate", "w_up", "w_down")}}
        part, s = share.apply(p, state, x, training=True)
        total = total + (part - shared)        # its routed part alone
        local += float(s["_counters"]["moe_pairs_local"])
        # the router is whole on every share: the same counts, the same bias
        np.testing.assert_array_equal(s["selection_bias"],
                                      new_state["selection_bias"])
    np.testing.assert_allclose(total, want, atol=3e-5)
    assert local == N * T * 2 == float(jnp.sum(counts))


# ------------------------------------------------------------------------- MTP

def test_the_shifted_loss_by_hand(both):
    logp = jax.nn.log_softmax(both["logits"], axis=-1)
    logp_1 = jax.nn.log_softmax(both["logits_1"], axis=-1)
    y = both["y"]
    main = -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))
    second = -jnp.mean(jnp.take_along_axis(
        logp_1[:, :-1], y[:, 1:, None], -1))
    assert float(both["loss"]) == pytest.approx(
        float(main + WEIGHT * second), abs=1e-5)
    # the last position of the second head carries no gradient
    criterion = nn.MultiTokenCrossEntropyCriterion(WEIGHT)
    g = jax.grad(lambda z: criterion._apply(
        Table({1: both["logits"], 2: z}), y))(both["logits_1"])
    assert float(jnp.max(jnp.abs(g[:, -1]))) == 0.0
    assert float(jnp.min(jnp.max(jnp.abs(g[:, :-1]), axis=-1))) > 0.0


def test_embedding_and_head_gradients_are_the_sum_of_their_uses(both):
    """Embedding and head serve the main model and the MTP module: each one
    leaf, its gradient what the two uses give apart, added."""
    model, params, state, x, y = (
        both[k] for k in ("model", "params", "state", "x", "y"))
    criterion = nn.MultiTokenCrossEntropyCriterion(WEIGHT)

    def loss(embed_main, embed_mtp, head_main, head_mtp):
        # two copies of each leaf: one reaches the main path, one the module
        embed = model.modules[0]
        calls = {"embed": 0, "head": 0}
        tree = {**params}

        def apply_with(name, first, second, orig):
            def _apply(p, s, v, training, rng):
                calls[name] += 1
                w = first if calls[name] == 1 else second
                return orig({"weight": w}, s, v, training, rng)
            return _apply

        head = model.modules[-2]
        embed_orig, head_orig = embed._apply, head._apply
        embed._apply = apply_with("embed", embed_main, embed_mtp, embed_orig)
        head._apply = apply_with("head", head_main, head_mtp, head_orig)
        try:
            out, _ = model.apply(tree, state, x, training=True)
        finally:
            del embed._apply, head._apply
        return criterion._apply(out, y)

    e, h = params["embed"]["weight"], params["head"]["weight"]
    g = jax.grad(loss, argnums=(0, 1, 2, 3))(e, e, h, h)
    for part in g:
        assert float(jnp.linalg.norm(part)) > 0
    assert _rel(g[0] + g[1], both["grads"]["embed"]) < 1e-5
    assert _rel(g[2] + g[3], both["grads"]["head"]) < 1e-5


@pytest.mark.parametrize("changed", [5, T - 1])
def test_a_later_token_changes_nothing_before_it(both, changed):
    """Causality, bit for bit, in both heads: the second head at position i
    reads token i + 1, so it may change from ``changed - 1`` on."""
    model, params, state, x = (both[k] for k in ("model", "params", "state", "x"))
    other = x.at[:, changed].set((x[:, changed] + 1) % CONFIG["vocab_size"])
    a, _ = model.apply(params, state, x, training=True)
    b, _ = model.apply(params, state, other, training=True)
    np.testing.assert_array_equal(a[1][:, :changed], b[1][:, :changed])
    np.testing.assert_array_equal(a[2][:, :changed - 1], b[2][:, :changed - 1])
    assert not np.array_equal(a[1][:, changed], b[1][:, changed])
    assert not np.array_equal(a[2][:, changed - 1], b[2][:, changed - 1])


def test_a_model_without_the_module_returns_logits_alone():
    config = {**CONFIG, "num_nextn_predict_layers": 0}
    model, params, state = _built(config)
    x, _ = _tokens(2)
    out, new_state = model.apply(params, state, x, training=True)
    assert out.shape == (N, T, CONFIG["vocab_size"]) and "mtp" not in params
    assert "mtp_loss" not in model.counters_tree(new_state)


def test_a_criterions_part_needs_a_slot_of_its_name():
    model, params, state = _built({**CONFIG, "num_nextn_predict_layers": 0})
    with pytest.raises(ValueError, match="mtp_loss"):
        model.with_counters(state, {"mtp_loss": jnp.zeros(())})


# ------------------------------------------------------------------ from_config

@pytest.mark.parametrize("change,names", [
    ({"n_group": 8}, ["n_group", "1"]),
    ({"topk_group": 4}, ["topk_group", "1"]),
    ({"scoring_func": "tanh"}, ["scoring_func", "sigmoid", "softmax"]),
    ({"rope_scaling": {"type": "yarn", "factor": 40}}, ["rope_scaling", "None"]),
    ({"num_nextn_predict_layers": 2}, ["num_nextn_predict_layers", "0", "1"]),
    ({"topk_method": "group_limited_greedy"}, ["topk_method", "noaux_tc"]),
    ({"q_lora_rank": None}, ["q_lora_rank", "integer"]),
    ({"moe_layer_freq": 2}, ["moe_layer_freq", "1"]),
    ({"tie_word_embeddings": True}, ["tie_word_embeddings"]),
    ({"scoring_func": "softmax"}, ["softmax", "greedy"]),
])
def test_from_config_names_what_it_accepts(change, names):
    with pytest.raises(ValueError) as e:
        decoder_lm.from_config({**CONFIG, **change})
    assert all(n in str(e.value) for n in names)


def test_dense_and_sparse_layers_follow_first_k_dense_replace():
    assert decoder_lm.mlp_layer_types({**CONFIG, "first_k_dense_replace": 2}) == [
        "dense", "dense", "sparse"]
    model = decoder_lm.from_config({**CONFIG, "first_k_dense_replace": 0,
                                    "num_nextn_predict_layers": 0})
    kinds = [type(m.modules[0].modules[3]).__name__ for m in model.modules
             if isinstance(m, nn.Remat)]
    assert kinds == ["RoutedExperts"] * 3
    with pytest.raises(ValueError, match="mlp_layer_types"):
        nn.DecoderLM(96, 32, ["full_attention"] * 2, 4, 4, 8, n_experts=4,
                     experts_per_token=2, expert_size=8,
                     mlp_layer_types=["dense"])
    with pytest.raises(ValueError, match="mlp_size"):
        nn.DecoderLM(96, 32, ["full_attention"] * 2, 4, 4, 8, n_experts=4,
                     experts_per_token=2, expert_size=8,
                     mlp_layer_types=["dense", "sparse"])


def test_a_greedy_sigmoid_router_keeps_no_bias():
    model, _, state = _built({**CONFIG, "topk_method": "greedy"})
    assert decoder_lm.reference_biases(state) == []
    assert "moe_bias_abs_max" not in model.counters_tree(state)


# ------------------------------------------------------------------ the reference

@pytest.mark.parametrize("operands", [None, "bfloat16"])
def test_the_benchmarks_copy_of_the_reference_gives_identical_outputs(
        both, operands):
    path = os.path.join(ROOT, "benchmark", "configs",
                        "joyai_llm_flash_reference.py")
    spec = importlib.util.spec_from_file_location("bench_latent_copy", path)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    rcfg = {**both["rcfg"], "operands": operands}
    at = jnp.asarray([[0, 5, T - 2], [7, 7, 3]])
    args = (both["rparams"], both["rbiases"], both["x"], both["y"], rcfg)
    a = ref.loss_and_grad(*args, at=at)
    b = copy.loss_and_grad(*args, at=at)
    assert a[3].shape == (N, 2, 3, CONFIG["vocab_size"])
    for u, v in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert jnp.array_equal(u, v)
    assert ref.routing_counters(a[2], rcfg) == copy.routing_counters(b[2], rcfg)


@pytest.mark.parametrize("fault", [
    {"rope_interleave": False}, {"softmax_scale": 8 ** -0.5},
    {"bias_in_weights": True}, {"shared_expert": False},
    {"mtp_loss_weight": 0.0}, {"experts_held": [1, 2, 3, 6]}],
    ids=lambda f: next(iter(f)))
def test_each_planted_fault_moves_the_references_answer(both, fault):
    """What the benchmark's controls plant: each one changes loss or
    gradients by far more than float32 rounding."""
    l, g, _, _ = ref.loss_and_grad(
        both["rparams"], both["rbiases"], both["x"], both["y"],
        {**both["rcfg"], **fault})
    worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        _rel, g, both["rgrads"])))
    assert abs(float(l) - float(both["rloss"])) > 1e-4 or worst > 1e-2
    assert worst > 1e-3


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

    config = {**CONFIG, "experts_held": [0, 1, 2, 5], "initializer_range": 0.02}
    p = 1.0 / np.arange(1, 97)
    tok = np.random.default_rng(0).choice(
        96, size=(16, T + 1), p=p / p.sum()).astype(np.int32)
    data = DataSet.array(tok[:, :-1].copy(), tok[:, 1:].copy(), batch_size=2)
    model = decoder_lm.from_config(config)
    opt = LocalOptimizer(model, data, nn.MultiTokenCrossEntropyCriterion(WEIGHT))
    opt.set_optim_method(Adam(learningrate=3e-3, beta1=0.9, beta2=0.95))
    keep = _Keep()
    tel = Telemetry(exporters=[keep])
    opt.set_telemetry(tel)
    opt.set_end_when(Trigger.max_iteration(24))
    opt.optimize()
    tel.close()
    steps = [r for r in keep.records if r.get("type") == "step"]
    assert len(steps) == 24
    assert steps[0]["loss"] == pytest.approx(1.1 * math.log(96), abs=0.5)
    assert np.median([r["loss"] for r in steps[-8:]]) < np.median(
        [r["loss"] for r in steps[:4]])
    for i, r in enumerate(steps):
        assert r["moe_dropped_pairs"] == 0.0
        assert r["moe_overflow_layers"] == 0.0   # reaches the step record
        assert 0 < r["moe_pairs_local"] <= 3 * N * T * 2
        assert r["moe_load_max_over_mean"] >= 1.0
        assert 0.0 < r["mtp_loss"] < 6.0
        # the bias moves by the rate a step at most, and does move
        assert 0.0 < r["moe_bias_abs_max"] <= 0.001 * (i + 1) + 1e-7
    assert steps[-1]["moe_bias_abs_max"] > steps[0]["moe_bias_abs_max"]
    assert steps[-1]["compile_count"] == 1
    assert sum(r["count"] for r in keep.records
               if r.get("type") == "compile") == 1
    # the model holds the biases the last step handed on
    biases = decoder_lm.reference_biases(model.get_state())
    assert len(biases) == 3
    assert max(float(jnp.max(jnp.abs(b))) for b in biases) == pytest.approx(
        steps[-1]["moe_bias_abs_max"])
