"""The gated-attention sparse model of the first family (Laguna-S-2.1's
mechanisms: a different query-head count on window and full layers, the
per-head sigmoid output gate, a partial YaRN rotary on the full layers, a
dense leading layer, a softmax router scaled by 2.5 beside a shared expert
behind its own sigmoid gate) against its plain float32 reference, at small
sizes on the CPU: D 64, layers (full, sliding x 3, full), 6 / 9 query heads
over 3 K/V heads of 16, window 8 at T 32, 16 experts top-4 of width 24 with a
shared expert of 24, a dense layer of 96, vocabulary 96."""

import hashlib
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.models import decoder_lm, decoder_lm_reference as ref
from bigdl_tpu.nn.attention import apply_rotary
from bigdl_tpu.nn.decoder import rope_inv_freq
from bigdl_tpu.ops.flash_attention import _dense_reference, flash_attention
from bigdl_tpu.utils.engine import Engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}
PLAIN = {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}
KINDS = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
CONFIG = dict(
    model_type="laguna", vocab_size=96, hidden_size=64, intermediate_size=96,
    num_hidden_layers=5, num_attention_heads=6, num_key_value_heads=3,
    head_dim=16, attention_bias=False, rms_norm_eps=1e-6, num_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=24,
    shared_expert_intermediate_size=24, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[0], tie_word_embeddings=False,
    gating="per-head", sliding_window=8,
    rope_parameters={"full_attention": YARN, "sliding_attention": PLAIN},
    layer_types=KINDS, moe_apply_router_weight_on_input=False,
    mlp_layer_types=["dense"] + ["sparse"] * 4, gating_types=["per_head"] * 5,
    moe_routed_scaling_factor=2.5,
    num_attention_heads_per_layer=[6, 9, 9, 9, 6],
    moe_router_logit_softcapping=0, initializer_range=0.125)
N, T = 2, 32
HELD = [0, 1, 2, 5, 8, 13]


def _tokens(seed):
    tok = np.random.default_rng(seed).integers(
        0, CONFIG["vocab_size"], (N, T + 1)).astype(np.int32)
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def _built(config, seed=0):
    model = decoder_lm.from_config(config)
    model.build(jax.random.PRNGKey(seed), jax.ShapeDtypeStruct((N, T), jnp.int32))
    return model, model.get_parameters(), model.get_state()


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _leaf(tree, path):
    for key in path.split("/"):
        tree = tree[int(key) if key.isdigit() else key]
    return tree


@pytest.fixture(scope="module")
def both():
    """The module's and the reference's loss, logits, gradients and counters
    on the same seeded weights and batch; 6 of the 16 experts held."""
    config = {**CONFIG, "experts_held": HELD}
    model, params, state = _built(config)
    x, y = _tokens(1)
    criterion = nn.TokenCrossEntropyCriterion()

    def loss(p):
        out, new_state = model.apply(p, state, x, training=True)
        return criterion._apply(out, y), (out, new_state)

    (l, (out, new_state)), grads = jax.jit(jax.value_and_grad(
        loss, has_aux=True))(params)
    rcfg = decoder_lm.reference_config(config)
    rparams = decoder_lm.reference_params(params)
    at = jnp.tile(jnp.arange(T), (N, 1))
    rl, rgrads, stats, picked = ref.loss_and_grad(rparams, x, y, rcfg, at=at)
    return dict(model=model, config=config, params=params, state=state, x=x,
                y=y, loss=l, logits=out, new_state=new_state,
                grads=decoder_lm.reference_params(grads), rcfg=rcfg,
                rparams=rparams, rloss=rl, rgrads=rgrads, stats=stats,
                rlogits=picked)


def test_from_config_builds_the_one_decoder_class(both):
    model = both["model"]
    assert type(model) is nn.DecoderLM
    blocks = [m.modules[0] for m in model.modules if isinstance(m, nn.Remat)]
    attn = [b.modules[1] for b in blocks]
    assert [a.num_heads for a in attn] == [6, 9, 9, 9, 6]
    assert [a.window for a in attn] == [None, 8, 8, 8, None]
    assert [a.rotary_dim for a in attn] == [8, None, None, None, 8]
    assert all(a.gate for a in attn)
    assert [type(b.modules[3]).__name__ for b in blocks] == [
        "GatedMLP"] + ["RoutedExperts"] * 4
    p = both["params"]
    assert {k: v.shape for k, v in p["layer_1"]["block"]["attn"].items()} == {
        "wq": (64, 144), "wk": (64, 48), "wv": (64, 48), "wo": (144, 64),
        "q_norm": (16,), "k_norm": (16,), "wg": (64, 9)}
    assert p["layer_0"]["block"]["attn"]["wg"].shape == (64, 6)
    assert {k: v.shape for k, v in p["layer_0"]["block"]["mlp"].items()} == {
        "w_in": (64, 192), "w_out": (96, 64)}
    assert {k: v.shape for k, v in p["layer_4"]["block"]["experts"].items()} == {
        "router": (64, 16), "w_gate": (6, 64, 24), "w_up": (6, 64, 24),
        "w_down": (6, 24, 64), "shared_in": (64, 48), "shared_out": (24, 64),
        "shared_gate": (64, 1)}
    experts = blocks[1].modules[3]
    assert experts.scoring == "softmax" and experts.routed_scaling == 2.5
    assert experts.shared_gate and experts.bias_update_rate is None


def test_module_loss_and_logits_match_the_reference(both):
    assert float(both["loss"]) == pytest.approx(float(both["rloss"]), abs=1e-5)
    np.testing.assert_allclose(both["logits"], both["rlogits"], atol=1e-5)


_ATTENTION = ("ln1", "wq", "wk", "wv", "q_norm", "k_norm", "wg", "wo", "ln2")
_SPARSE = ("router", "w_gate", "w_up", "w_down", "shared_in", "shared_out",
           "shared_gate")
LEAVES = ["embed", "final_norm", "head"] + [
    f"layers/0/{k}" for k in _ATTENTION + ("w_in", "w_out")] + [
    f"layers/{i}/{k}" for i in range(1, 5) for k in _ATTENTION + _SPARSE]


@pytest.mark.parametrize("leaf", LEAVES)
def test_module_gradient_leaf_matches_the_reference(both, leaf):
    got, want = _leaf(both["grads"], leaf), _leaf(both["rgrads"], leaf)
    assert got.shape == want.shape
    assert float(jnp.linalg.norm(want)) > 0
    assert _rel(got, want) < 3e-5


def test_every_gradient_leaf_is_compared(both):
    paths = {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
             for p, _ in jax.tree_util.tree_leaves_with_path(both["rgrads"])}
    assert paths == set(LEAVES)


def test_counters_match_the_references_own(both):
    got = {k: float(v) for k, v in
           both["model"].counters_tree(both["new_state"]).items()}
    want = ref.routing_counters(both["stats"])
    assert set(want) == {"moe_pairs_local", "moe_load_max_over_mean",
                         "moe_dropped_pairs", "attn_gate_mean"}
    assert set(got) == set(want) | {"moe_overflow_layers"}
    assert got["moe_pairs_local"] == want["moe_pairs_local"] > 0
    assert got["moe_load_max_over_mean"] == pytest.approx(
        want["moe_load_max_over_mean"], rel=1e-6)
    assert got["moe_dropped_pairs"] == 0.0 == got["moe_overflow_layers"]
    # the mean over tokens, heads and the five gated layers
    assert got["attn_gate_mean"] == pytest.approx(want["attn_gate_mean"],
                                                  abs=1e-6)
    assert 0.1 < got["attn_gate_mean"] < 0.9
    # four sparse layers' routed pairs, over all 16 experts
    assert both["stats"]["counts"].shape == (4, len(HELD))


def test_the_gate_is_sigmoid_of_x_w_g_by_hand():
    """One layer: each head's output times sigmoid(x W_g) at that token and
    head, before W_o; the counter is the gate's mean."""
    m = nn.GroupedQueryAttention(9, 3, 16, rope=PLAIN, gate=True,
                                 init_std=0.3)
    x = jax.random.normal(jax.random.PRNGKey(0), (N, T, 64))
    m.build(jax.random.PRNGKey(1), jax.ShapeDtypeStruct(x.shape, x.dtype))
    params = m.get_parameters()
    out, state = m.apply(params, m.get_state(), x)
    bare = nn.GroupedQueryAttention(9, 3, 16, rope=PLAIN, init_std=0.3)
    bare.build(jax.random.PRNGKey(1), jax.ShapeDtypeStruct(x.shape, x.dtype))
    plain = {k: v for k, v in params.items() if k != "wg"}
    assert sorted(bare.get_parameters()) == sorted(plain)
    # the context before W_o: the ungated layer with W_o the identity
    ctx, _ = bare.apply({**plain, "wo": jnp.eye(144)},
                        bare.get_state(), x)
    ctx = ctx.reshape(N, T, 9, 16)
    g = 1.0 / (1.0 + np.exp(-np.einsum("ntd,dh->nth", np.asarray(x),
                                       np.asarray(params["wg"]))))
    want = np.einsum("nthe,hef->ntf", np.asarray(ctx) * g[..., None],
                     np.asarray(params["wo"]).reshape(9, 16, 64))
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert float(state["_counters"]["attn_gate_mean"]) == pytest.approx(
        float(g.mean()), abs=1e-6)
    # without the gate nothing of it is in the tree or the state
    assert bare.get_state() == {} and "wg" not in bare.get_parameters()


def test_partial_yarn_by_hand():
    """The full layers' rotary: YaRN over the first 64 of 128 features,
    frequencies reckoned over 64 (d(b) = 64 ln(8192 / (2 pi b)) / (2 ln
    500000): lo 9, hi 18), cos and sin times 1.4852, features 64-127 passed
    through as they are."""
    inv, factor = rope_inv_freq(YARN, 64)
    assert factor == 1.4852030263919618
    d_fast = 64 * math.log(8192 / (2 * math.pi * 32)) / (2 * math.log(500000))
    d_slow = 64 * math.log(8192 / (2 * math.pi * 1)) / (2 * math.log(500000))
    lo, hi = math.floor(d_fast), math.ceil(d_slow)
    assert (lo, hi) == (9, 18) and inv.shape == (32,)
    want = []
    for i in range(32):
        base = 500000.0 ** (-2 * i / 64)
        r = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want.append((1 - r) * base + r * base / 128)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    attn = nn.GroupedQueryAttention(48, 8, 128, rope=YARN)
    assert attn.rotary_dim == 64
    np.testing.assert_array_equal(attn._rope[0], inv)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 5, 128)),
                    jnp.float32)
    pos = jnp.arange(5) + 300
    got = apply_rotary(x, pos, inv, factor, rotary_dim=64)
    ang = np.asarray(pos, np.float64)[:, None] * np.asarray(want)[None, :]
    cos, sin = np.cos(ang) * factor, np.sin(ang) * factor
    x1, x2 = np.asarray(x[..., :32]), np.asarray(x[..., 32:64])
    np.testing.assert_allclose(got[..., :32], x1 * cos - x2 * sin, atol=2e-4)
    np.testing.assert_allclose(got[..., 32:64], x1 * sin + x2 * cos, atol=2e-4)
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    # the reference reckons and turns on its own
    rinv, rfactor = ref.rope_inv_freq(YARN, 64)
    np.testing.assert_allclose(inv, rinv, rtol=1e-5)
    np.testing.assert_allclose(
        ref.rotate(x, rinv, rfactor, 64),
        apply_rotary(x, jnp.arange(5), inv, factor, rotary_dim=64), atol=1e-5)
    # a whole-head rotary is the call it always was
    plain = nn.GroupedQueryAttention(72, 8, 128, window=512, rope=PLAIN)
    assert plain.rotary_dim is None
    np.testing.assert_array_equal(plain._rope[0], rope_inv_freq(PLAIN, 128)[0])


def test_the_scaled_softmax_router_by_hand():
    from bigdl_tpu.nn.moe import route_top_k

    x = jax.random.normal(jax.random.PRNGKey(0), (5, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    p, e = route_top_k(x, w, 4, 2.5)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(x @ w, axis=-1), 4)
    np.testing.assert_array_equal(e, top_e)
    np.testing.assert_allclose(p, 2.5 * top_p / top_p.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(p.sum(-1), 2.5, rtol=1e-6)


def test_the_shares_and_the_shared_expert_once_add_up_to_the_whole_layer():
    """Experts 0-3, 4-7, 8-11, 12-15 of 16 on four chips: the four shares'
    routed parts plus the gated shared expert counted once give what the
    uncut reference gives for the whole layer."""
    kw = dict(routed_scaling=2.5, shared_size=24, shared_gate=True,
              init_std=0.3)
    whole = nn.RoutedExperts(16, 24, 4, **kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (N * T, 64))
    whole.build(jax.random.PRNGKey(1), jax.ShapeDtypeStruct(x.shape, x.dtype))
    params = whole.get_parameters()
    rcfg = dict(num_experts_per_tok=4, routed_scaling=2.5,
                shared_expert_gate=True, experts_held=list(range(16)))
    want, counts = ref.experts(x, params, rcfg)
    got, _ = whole.apply(params, whole.get_state(), x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    shared = ref.swiglu(x, params["shared_in"], params["shared_out"]) \
        * jax.nn.sigmoid(x @ params["shared_gate"])
    total, local = shared, 0.0
    for first in (0, 4, 8, 12):
        held = list(range(first, first + 4))
        share = nn.RoutedExperts(16, 24, 4, experts_held=held, **kw)
        share.build(jax.random.PRNGKey(1), jax.ShapeDtypeStruct(x.shape, x.dtype))
        idx = jnp.asarray(held)
        p = {**params, **{k: params[k][idx] for k in ("w_gate", "w_up", "w_down")}}
        part, s = share.apply(p, share.get_state(), x)
        total = total + (part - shared)        # its routed part alone
        local += float(s["_counters"]["moe_pairs_local"])
    np.testing.assert_allclose(total, want, atol=3e-5)
    assert local == N * T * 4 == float(jnp.sum(counts))


@pytest.mark.parametrize("k,n,tiles", [
    (2304, 896, (512, 768, 896)), (896, 2304, (512, 896, 768)),    # Mellum2
    (2048, 768, (512, 1024, 768)), (768, 2048, (512, 768, 1024)),  # JoyAI
    (2688, 1856, (512, 896, 640)), (1856, 2688, (512, 640, 896)),  # Nemotron
    (3072, 1024, (512, 1024, 512)), (1024, 3072, (512, 1024, 768)),
])
def test_the_transposed_products_tiles_cut_only_what_passed_the_budget(
        k, n, tiles):
    """The grouped kernel's transposed product keeps a (K, N) float32 tile:
    the other models' tiles are the forward rule's, the 3072-wide model's
    1024 x 1024 is cut in its columns (17 MiB of the 16 a v5e kernel may
    have, ``tests/test_latent_moe_lm.py`` compiles it)."""
    from bigdl_tpu.nn.moe import _tiling, _transposed_tiling

    assert _transposed_tiling(k, n) == tiles
    rows, tk, tn = tiles
    assert tk * tn <= 768 * 1024
    assert (_tiling(k, n) == tiles) == (3072 not in (k, n))


@pytest.mark.parametrize("k,n", [(1024, 3072), (3072, 1024)],
                         ids=["w_down", "w_gate"])
def test_the_transposed_product_at_its_cut_tiles_matches_the_xla_form(
        monkeypatch, k, n):
    """The grouped kernel's transposed product (each expert's weight
    gradient) at the 3072-wide model's widths and its cut tiles, through the
    interpreter, against ``jax.lax.ragged_dot_general``: bfloat16 operands
    with float32 sums, groups that start inside a row tile, an empty group,
    and rows past the groups' total that must not count."""
    import functools
    from types import SimpleNamespace

    from bigdl_tpu.nn import moe

    sizes = jnp.asarray([300, 0, 211, 5, 512, 130, 77, 29], jnp.int32)
    rows = 1536                                    # 1264 in groups
    rng = np.random.default_rng(7)
    lhs = jnp.asarray(rng.standard_normal((rows, k)), jnp.bfloat16)
    g = jnp.asarray(rng.standard_normal((rows, n)), jnp.bfloat16)
    past = jnp.arange(rows)[:, None] >= int(jnp.sum(sizes))
    lhs, g = jnp.where(past, 1e4, lhs), jnp.where(past, -1e4, g)
    want = np.asarray(moe._grouped_transposed(lhs, g, sizes, jnp.float32))
    kernels = moe._megablox()
    monkeypatch.setattr(moe, "_megablox", lambda: SimpleNamespace(
        tgmm=functools.partial(kernels.tgmm, interpret=True)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = np.asarray(moe._grouped_transposed(lhs, g, sizes, jnp.float32))
    assert moe._transposed_tiling(k, n) != moe._tiling(k, n)
    assert got.shape == want.shape == (8, k, n)
    assert not np.any(got[1]) and not np.any(want[1])
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * np.abs(want).max())


# ------------------------------------------------------------------ the kernel

@pytest.mark.parametrize("h,window", [(9, None), (9, 100), (6, None), (6, 100)],
                         ids=["9-full", "9-window", "6-full", "6-window"])
def test_flash_at_nine_and_six_query_heads_a_kv_head_matches_dense(h, window):
    """The cell's groups (72 and 48 query heads over 8 K/V heads) over one
    K/V head: values and dQ, dK, dV against XLA attention, T 256 in tiles of
    128, with and without a window."""
    t, d = 256, 16
    ks = jax.random.split(jax.random.PRNGKey(h), 4)
    q = jax.random.normal(ks[0], (1, h, t, d))
    k, v = (jax.random.normal(kk, (1, 1, t, d)) for kk in ks[1:3])
    cot = jax.random.normal(ks[3], (1, h, t, d))
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, True, interpret=True, block_q=128, block_k=128,
        window=window), q, k, v)
    want, rvjp = jax.vjp(lambda q, k, v: _dense_reference(
        q, k, v, True, None, window), q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref_, like in zip(vjp(cot), rvjp(cot), (q, k, v)):
        assert got.shape == like.shape
        np.testing.assert_allclose(got, ref_, atol=5e-5)


# ------------------------------------------------------------------ from_config

@pytest.mark.parametrize("change,names", [
    ({"moe_router_logit_softcapping": 30.0},
     ["moe_router_logit_softcapping", "0"]),
    ({"moe_apply_router_weight_on_input": True},
     ["moe_apply_router_weight_on_input", "False"]),
    ({"gating": "elementwise"}, ["gating", "per-head"]),
    ({"gating_types": ["elementwise"] * 5}, ["gating_types", "per_head"]),
    ({"gating": None}, ["gating", "per_head"]),
    ({"mlp_only_layers": []}, ["mlp_layer_types", "mlp_only_layers"]),
    ({"decoder_sparse_step": 2}, ["mlp_layer_types", "decoder_sparse_step"]),
    ({"mlp_layer_types": ["dense", "moe"] * 3}, ["mlp_layer_types", "sparse"]),
    ({"num_attention_heads_per_layer": [6, 9]}, ["num_attention_heads_per_layer"]),
    ({"num_attention_heads_per_layer": [6, 9, 9, 8, 6]}, ["query heads", "K/V"]),
    ({"partial_rotary_factor": 0.5}, ["partial_rotary_factor",
                                      "rope_parameters"]),
    ({"rope_parameters": {"full_attention": {**YARN, "partial_rotary_factor":
                                             0.3}, "sliding_attention": PLAIN}},
     ["partial_rotary_factor", "even"]),
])
def test_from_config_names_what_it_accepts(change, names):
    with pytest.raises(ValueError) as e:
        decoder_lm.from_config({**CONFIG, **change})
    assert all(n in str(e.value) for n in names), str(e.value)


def test_the_module_arguments_refuse_what_they_do_not_build():
    with pytest.raises(ValueError, match="sigmoid"):
        nn.RoutedExperts(16, 24, 4, bias_update_rate=0.001)
    with pytest.raises(ValueError, match="shared_size"):
        nn.RoutedExperts(16, 24, 4, shared_gate=True)
    with pytest.raises(ValueError, match="layer_heads"):
        nn.DecoderLM(96, 64, KINDS, 6, 3, 16, n_experts=4, experts_per_token=2,
                     expert_size=8, layer_heads=[6, 9])


def test_the_published_config_builds_by_shape_inference_alone():
    """All 48 layers, 256 experts, the whole vocabulary: the card's 118B,
    counted from shapes, nothing allocated."""
    published = {**CONFIG, **dict(
        vocab_size=100352, hidden_size=3072, intermediate_size=12288,
        num_hidden_layers=48, num_attention_heads=48, num_key_value_heads=8,
        head_dim=128, num_experts=256, num_experts_per_tok=10,
        moe_intermediate_size=1024, shared_expert_intermediate_size=1024,
        sliding_window=512, layer_types=KINDS[:4] * 12,
        mlp_layer_types=["dense"] + ["sparse"] * 47,
        gating_types=["per_head"] * 48,
        num_attention_heads_per_layer=[48, 72, 72, 72] * 12)}
    model = decoder_lm.from_config(published)
    shapes = jax.eval_shape(
        lambda: (model.build(jax.random.PRNGKey(0),
                             jax.ShapeDtypeStruct((1, 256), jnp.int32)),
                 model.get_parameters())[1])
    assert sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes)) \
        == 117_562_109_952
    assert shapes["layer_1"]["block"]["attn"]["wq"].shape == (3072, 9216)
    assert shapes["layer_4"]["block"]["attn"]["wg"].shape == (3072, 48)
    assert shapes["layer_0"]["block"]["mlp"]["w_in"].shape == (3072, 24576)


# ------------------------------------------------------------ the other models

M_CONFIG = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=8,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 2,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    sliding_window=8, rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    rms_norm_eps=1e-6, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, norm_topk_prob=True, experts_held=[0, 1, 2, 3])
N_CONFIG = dict(
    vocab_size=64, hidden_size=32, num_hidden_layers=5,
    hybrid_override_pattern="EM*EM", mamba_num_heads=8, mamba_head_dim=8,
    ssm_state_size=16, n_groups=4, conv_kernel=4, chunk_size=8,
    use_conv_bias=True, mamba_proj_bias=False, mlp_hidden_act="relu2",
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=3,
    norm_topk_prob=True, routed_scaling_factor=2.5, n_group=1, topk_group=1,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    layer_norm_epsilon=1e-5, tie_word_embeddings=False,
    initializer_range=0.125, router_bias_update_rate=0.001,
    experts_held=[0, 1, 5])
G_CONFIG = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=3,
    layer_types=["mamba", "attention", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, rms_norm_eps=1e-5,
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4,
    mamba_chunk_size=8, mamba_expand=2, mamba_n_groups=1,
    mamba_conv_bias=True, mamba_proj_bias=False, shared_intermediate_size=48,
    num_local_experts=0, attention_multiplier=0.2, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=8,
    position_embedding_type="nope", tie_word_embeddings=True)
# read from the tree before the gate, the per-layer heads, the partial rotary,
# the dense layer and the scaled router came in (M, N), and before the
# post-norm block and the whole-width q/k norm came in (G: a pre-norm block
# with scaled residuals around NoPE attention without q/k norm; L: this
# file's gated model): (digest of the loss-and-gradient program's jaxpr, the
# loss, digest of the parameter tree's names and shapes). A change that
# rightly changes these models' programs updates them; one that meant to
# leave them alone fails here.
BEFORE = {
    ("M", "float32"): ("6458878d3c749716", 4.854368209838867, "8c76a6ddab395dc6"),
    ("M", "bfloat16"): ("bd2d3721df03b578", 4.854185104370117, "8c76a6ddab395dc6"),
    ("N", "float32"): ("4ff1c27797bcc39d", 4.3778862953186035, "0ba5515738b6b412"),
    ("N", "bfloat16"): ("4c38955bcebfb18e", 4.3778204917907715, "0ba5515738b6b412"),
    ("G", "float32"): ("78cb1896e6f3cadc", 4.565230369567871, "de0d37e8903b11b6"),
    ("G", "bfloat16"): ("0f8e25c9284696a8", 4.56523323059082, "de0d37e8903b11b6"),
    ("L", "float32"): ("1385f6488a692e20", 4.981200218200684, "1fe5814f8d21ee6a"),
    ("L", "bfloat16"): ("4a63f5ada92b27b4", 4.990013122558594, "1fe5814f8d21ee6a"),
}


@pytest.mark.parametrize("name,compute", sorted(BEFORE))
def test_the_sparse_and_hybrid_models_build_the_program_they_built(
        name, compute):
    config = {"M": M_CONFIG, "N": N_CONFIG, "G": G_CONFIG, "L": CONFIG}[name]
    prev = Engine.compute_dtype()
    Engine.set_compute_dtype(compute)
    try:
        model = decoder_lm.from_config(config)
        model.build(jax.random.PRNGKey(0),
                    jax.ShapeDtypeStruct((2, 32), jnp.int32))
        params, state = model.get_parameters(), model.get_state()
        tok = np.random.default_rng(1).integers(
            0, config["vocab_size"], (2, 33)).astype(np.int32)
        x, y = jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])
        criterion = nn.TokenCrossEntropyCriterion()

        def loss(p):
            out, new_state = model.apply(p, state, x, training=True)
            return criterion._apply(out, y), new_state

        step = jax.value_and_grad(loss, has_aux=True)
        text = re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(step)(params)))
        value = jax.jit(lambda p: loss(p)[0])(params)
    finally:
        Engine.set_compute_dtype(prev)
    names = [(jax.tree_util.keystr(p), a.shape)
             for p, a in jax.tree_util.tree_leaves_with_path(params)]
    program, loss_value, tree = BEFORE[(name, compute)]
    assert hashlib.sha256(repr(names).encode()).hexdigest()[:16] == tree
    assert float(value) == pytest.approx(loss_value, abs=1e-5)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == program


# ------------------------------------------------------------------ the reference

@pytest.mark.parametrize("operands", [None, "bfloat16"])
def test_the_benchmarks_copy_of_the_reference_gives_identical_outputs(
        both, operands):
    path = os.path.join(ROOT, "benchmark", "configs", "laguna_s_2_1_reference.py")
    spec = importlib.util.spec_from_file_location("bench_laguna_copy", path)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    rcfg = {**both["rcfg"], "operands": operands}
    at = jnp.asarray([[0, 5, T - 2], [7, 7, 3]])
    args = (both["rparams"], both["x"], both["y"], rcfg)
    a = ref.loss_and_grad(*args, at=at)
    b = copy.loss_and_grad(*args, at=at)
    assert a[3].shape == (N, 3, CONFIG["vocab_size"])
    for u, v in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert jnp.array_equal(u, v)
    assert ref.routing_counters(a[2]) == copy.routing_counters(b[2])


@pytest.mark.parametrize("fault", [
    {"attention_gate": False},
    {"rope_parameters": {"full_attention": {**YARN, "partial_rotary_factor": 1},
                         "sliding_attention": PLAIN}},
    {"routed_scaling": 1.0}, {"shared_expert_gate": False}],
    ids=["ungated", "whole-head-rotary", "unscaled", "shared-ungated"])
def test_each_planted_fault_moves_the_references_answer(both, fault):
    """What the benchmark's controls plant: each one changes loss or
    gradients by far more than float32 rounding."""
    l, g, _, _ = ref.loss_and_grad(both["rparams"], both["x"], both["y"],
                                   {**both["rcfg"], **fault})
    worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        _rel, g, both["rgrads"])))
    assert abs(float(l) - float(both["rloss"])) > 1e-4 or worst > 1e-2
    assert worst > 1e-3


@pytest.mark.parametrize("changed", [5, T - 1])
def test_a_later_token_changes_nothing_before_it(both, changed):
    model, params, state, x = (both[k] for k in ("model", "params", "state", "x"))
    other = x.at[:, changed].set((x[:, changed] + 1) % CONFIG["vocab_size"])
    a, _ = model.apply(params, state, x, training=False)
    b, _ = model.apply(params, state, other, training=False)
    np.testing.assert_array_equal(a[:, :changed], b[:, :changed])
    assert float(jnp.max(jnp.abs(a[:, changed] - b[:, changed]))) > 1e-4


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


def test_language_model_trains_through_optimize_with_the_gate_in_the_record():
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.obs import Telemetry
    from bigdl_tpu.optim import Adam, LocalOptimizer
    from bigdl_tpu.optim.trigger import Trigger

    config = {**CONFIG, "experts_held": HELD, "initializer_range": 0.02}
    p = 1.0 / np.arange(1, 97)
    tok = np.random.default_rng(0).choice(
        96, size=(16, T + 1), p=p / p.sum()).astype(np.int32)
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
    assert steps[0]["loss"] == pytest.approx(math.log(96), abs=0.5)
    assert np.median([r["loss"] for r in steps[-8:]]) < np.median(
        [r["loss"] for r in steps[:4]])
    for r in steps:
        # N(0, 0.02) gate weights over a unit-RMS input: g near one half
        assert 0.4 < r["attn_gate_mean"] < 0.6
        assert r["moe_dropped_pairs"] == 0.0
    assert steps[-1]["compile_count"] == 1
