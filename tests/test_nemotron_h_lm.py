"""The state-space / attention / routed-experts hybrid whose every layer is
one mixer (nn.MixerBlock, the ``"experts"`` layer kind, nn.Mamba2Mixer with
several B/C groups and a grouped gated norm, nn.RoutedExperts' ``relu2``
form) against its plain float32 reference, at small sizes on the CPU: D 32,
pattern ``EM*EM``, 8 heads of 8 in 4 B/C groups of state 16, attention 4 / 2
heads of 8, 8 experts top-3 of width 24 with a shared expert of 48,
vocabulary 64."""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.models import decoder_lm, nemotron_h_lm_reference as ref
from bigdl_tpu.nn import moe
from bigdl_tpu.utils.engine import Engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
CONFIG = dict(
    vocab_size=64, hidden_size=32, num_hidden_layers=5,
    hybrid_override_pattern="EM*EM", mamba_num_heads=8, mamba_head_dim=8,
    ssm_state_size=16, n_groups=4, conv_kernel=4, chunk_size=8,
    use_conv_bias=True, mamba_proj_bias=False, mlp_hidden_act="relu2",
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=3,
    norm_topk_prob=True, routed_scaling_factor=2.5, n_group=1, topk_group=1,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    layer_norm_epsilon=1e-5, tie_word_embeddings=False,
    initializer_range=0.125, router_bias_update_rate=0.001)
N, T = 2, 32


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


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _both(compute_dtype):
    """The module's and the reference's loss, logits, gradients, counters and
    biases after the step on the same seeded weights, biases and batch;
    experts 0, 1 and 5 of 8 held; the module in ``compute_dtype``, the
    reference with those operands."""
    config = {**CONFIG, "experts_held": [0, 1, 5]}
    prev = Engine.compute_dtype()
    Engine.set_compute_dtype(compute_dtype)
    try:
        model = decoder_lm.from_config(config)
        model.build(jax.random.PRNGKey(0),
                    jax.ShapeDtypeStruct((N, T), jnp.int32))
        params, state = model.get_parameters(), _with_biases(model.get_state())
        x, y = _tokens(1)
        criterion = nn.TokenCrossEntropyCriterion()

        def loss(p):
            out, new_state = model.apply(p, state, x, training=True)
            return criterion._apply(out, y), (out, new_state)

        (l, (out, new_state)), grads = jax.value_and_grad(
            loss, has_aux=True)(params)
    finally:
        Engine.set_compute_dtype(prev)
    rcfg = {**decoder_lm.reference_config(config),
            "operands": None if compute_dtype == "float32" else compute_dtype}
    rparams = decoder_lm.reference_params(params)
    rbiases = decoder_lm.reference_biases(state)
    at = jnp.tile(jnp.arange(T), (N, 1))
    rl, rgrads, stats, picked = ref.loss_and_grad(
        rparams, rbiases, x, y, rcfg, at=at)
    return dict(model=model, config=config, params=params, state=state,
                x=x, y=y, loss=l, logits=out, new_state=new_state,
                grads=decoder_lm.reference_params(grads), rcfg=rcfg,
                rparams=rparams, rbiases=rbiases, rloss=rl, rgrads=rgrads,
                stats=stats, rlogits=picked)


@pytest.fixture(scope="module")
def both():
    return _both("float32")


@pytest.fixture(scope="module")
def both_bf16():
    return _both("bfloat16")


def test_from_config_builds_the_one_decoder_class(both):
    model = both["model"]
    assert type(model) is nn.DecoderLM
    blocks = [m.modules[0] for m in model.modules if isinstance(m, nn.Remat)]
    assert all(type(b) is nn.MixerBlock and len(b.modules) == 2 for b in blocks)
    assert [type(b.modules[1]).__name__ for b in blocks] == [
        "RoutedExperts", "Mamba2Mixer", "GroupedQueryAttention",
        "RoutedExperts", "Mamba2Mixer"]
    assert [b.modules[1].name() for b in blocks] == [
        "experts", "ssm", "attn", "experts", "ssm"]
    first = both["params"]["layer_0"]["block"]
    assert sorted(first) == ["experts", "ln"]
    # two matrices an expert, and a shared expert of the same form
    assert {k: v.shape for k, v in first["experts"].items()} == {
        "router": (32, 8), "w_up": (3, 32, 24), "w_down": (3, 24, 32),
        "shared_in": (32, 48), "shared_out": (48, 32)}
    ssm_leaves = both["params"]["layer_1"]["block"]["ssm"]
    assert ssm_leaves["in_proj"].shape == (32, 64 + 64 + 2 * 4 * 16 + 8)
    assert ssm_leaves["conv_w"].shape == (64 + 2 * 4 * 16, 4)
    attn = blocks[2].modules[1]
    assert attn._rope is None and attn._norm is None and attn.scale is None


def test_module_loss_and_logits_match_the_reference(both):
    assert float(both["loss"]) == pytest.approx(float(both["rloss"]), abs=1e-5)
    np.testing.assert_allclose(both["logits"], both["rlogits"], atol=1e-5)


_MAMBA = ("ln", "in_proj", "conv_w", "conv_b", "A_log", "dt_bias", "D", "norm",
          "out_proj")
_EXPERTS = ("ln", "router", "w_up", "w_down", "shared_in", "shared_out")
_ATTENTION = ("ln", "wq", "wk", "wv", "wo")
LEAVES = ["embed", "final_norm", "head"] + [
    f"layers/{i}/{k}" for i, kinds in enumerate(
        (_EXPERTS, _MAMBA, _ATTENTION, _EXPERTS, _MAMBA)) for k in kinds]


def _leaf(tree, path):
    for key in path.split("/"):
        tree = tree[int(key) if key.isdigit() else key]
    return tree


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


def test_counters_and_the_bias_after_the_step_match_the_reference(both):
    got = {k: float(v) for k, v in
           both["model"].counters_tree(both["new_state"]).items()}
    want = ref.counters(both["stats"], both["rcfg"], N, T)
    assert set(want) == {
        "ssm_log_decay_min", "ssm_state_rms", "moe_pairs_local",
        "moe_load_max_over_mean", "moe_dropped_pairs", "moe_bias_abs_max"}
    assert set(got) == set(want) | {"moe_overflow_layers"}
    assert got["moe_overflow_layers"] == 0.0 == got["moe_dropped_pairs"]
    assert got["moe_pairs_local"] == want["moe_pairs_local"] > 0
    for name in ("moe_load_max_over_mean", "ssm_log_decay_min", "ssm_state_rms",
                 "moe_bias_abs_max"):
        assert got[name] == pytest.approx(want[name], rel=1e-5)
    assert got["ssm_log_decay_min"] < 0 < got["ssm_state_rms"]
    after = decoder_lm.reference_biases(both["new_state"])
    assert len(after) == len(both["stats"]["biases"]) == 2
    for b, rb, before in zip(after, both["stats"]["biases"], both["rbiases"]):
        np.testing.assert_array_equal(b, rb)
        moved = np.abs(np.asarray(b - before))
        assert set(np.round(moved / 0.001, 3)) <= {0.0, 1.0} and moved.max() > 0
    # every pair of every token is counted, over all 8 experts
    np.testing.assert_array_equal(
        jnp.sum(both["stats"]["counts"], axis=-1), [N * T * 3] * 2)


def test_at_bfloat16_operands_the_module_matches_the_reference(both_bf16):
    """The stated precision: the module in bfloat16 compute against the
    float32 reference whose products round their operands to bfloat16. The
    chunked scan rounds the operands of its own four products, which the
    recurrence does not have: the bands are bfloat16's, not float32's."""
    b = both_bf16
    assert float(b["loss"]) == pytest.approx(float(b["rloss"]), abs=2e-3)
    np.testing.assert_allclose(b["logits"], b["rlogits"], atol=0.05)
    worst = max(_rel(_leaf(b["grads"], leaf), _leaf(b["rgrads"], leaf))
                for leaf in LEAVES)
    assert worst < 0.08
    # and the precisions are told apart: float32 reads a thousand times closer
    assert _rel(b["grads"]["head"], b["rgrads"]["head"]) > 1e-5
    got = {k: float(v) for k, v in
           b["model"].counters_tree(b["new_state"]).items()}
    want = ref.counters(b["stats"], b["rcfg"], N, T)
    assert got["moe_pairs_local"] == pytest.approx(
        want["moe_pairs_local"], abs=4)
    assert got["ssm_state_rms"] == pytest.approx(want["ssm_state_rms"], rel=0.02)


def test_the_bias_is_state_with_no_gradient_and_no_optimizer_slot(both):
    from bigdl_tpu.optim import Adam

    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(both["params"])]
    assert not any("bias" in n and "dt_bias" not in n for n in names)
    slots = Adam().init_slots(both["params"])
    assert not any("selection_bias" in jax.tree_util.keystr(p) for p, _ in
                   jax.tree_util.tree_leaves_with_path(slots))
    model, params, x = (both[k] for k in ("model", "params", "x"))
    _, held = model.apply(params, both["state"], x, training=False)
    for b, before in zip(decoder_lm.reference_biases(held), both["rbiases"]):
        np.testing.assert_array_equal(b, before)


# ---------------------------------------------------------------- the share test

def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_whole_layer():
    """The routed parts of all 16 shares of an ``E`` layer (16 experts, one
    held a share) plus the shared expert counted once give what the uncut
    reference's layer gives."""
    kw = dict(scoring="sigmoid", routed_scaling=2.5, bias_update_rate=0.001,
              shared_size=20, init_std=0.3, form="relu2")
    whole = nn.RoutedExperts(16, 12, 3, **kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (N * T, 32))
    whole.build(jax.random.PRNGKey(1), jax.ShapeDtypeStruct(x.shape, x.dtype))
    params = whole.get_parameters()
    assert sorted(params) == ["router", "shared_in", "shared_out", "w_down",
                              "w_up"]
    state = {**whole.get_state(), "selection_bias":
             0.1 * jax.random.normal(jax.random.PRNGKey(2), (16,))}
    rcfg = dict(num_experts_per_tok=3, routed_scaling_factor=2.5,
                experts_held=list(range(16)))
    want, counts = ref.experts(x, params, state["selection_bias"], rcfg)
    got, new_state = whole.apply(params, state, x, training=True)
    np.testing.assert_allclose(got, want, atol=3e-5)
    shared = ref.relu2_mlp(x, params["shared_in"], params["shared_out"], {})
    total, local = shared, 0.0
    for e in range(16):
        share = nn.RoutedExperts(16, 12, 3, experts_held=[e], **kw)
        share.build(jax.random.PRNGKey(1), jax.ShapeDtypeStruct(x.shape, x.dtype))
        p = {**params, **{k: params[k][e:e + 1] for k in ("w_up", "w_down")}}
        part, s = share.apply(p, state, x, training=True)
        total = total + (part - shared)        # its routed part alone
        local += float(s["_counters"]["moe_pairs_local"])
        # the router is whole on every share: the same counts, the same bias
        np.testing.assert_array_equal(s["selection_bias"],
                                      new_state["selection_bias"])
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert local == N * T * 3 == float(jnp.sum(counts))


# --------------------------------------------------------- the experts' two forms

def _dense_relu2(x, params, held, top_k, bias=None):
    """A dense loop over the experts held: every expert over every token."""
    w, ids = moe.route_sigmoid_top_k(x, params["router"], bias, top_k, 2.5)
    out = jnp.zeros_like(x)
    for slot, e in enumerate(held):
        weight = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        hidden = jnp.square(jax.nn.relu(x @ params["w_up"][slot]))
        out = out + weight[:, None] * (hidden @ params["w_down"][slot])
    return out


@pytest.mark.parametrize("held,rows,overflows", [
    (tuple(range(8)), 96 * 3, 0),      # all held: one pass, pairs gather
    ((2, 5), 512, 0),                  # a share: the sized buffer, one block
    ((2, 5), 16, 1),                   # a buffer the step overflows: the loop
], ids=["one-pass", "sized-buffer", "overflowing-step"])
def test_relu2_experts_match_a_dense_loop_over_the_experts(
        monkeypatch, held, rows, overflows):
    if overflows:
        monkeypatch.setattr(moe, "buffer_rows", lambda pairs, h, e: rows)
    t, d, f, k = 96, 32, 24, 3
    assert moe.buffer_rows(t * k, len(held), 8) == min(rows, t * k)
    layer = nn.RoutedExperts(8, f, k, experts_held=held, scoring="sigmoid",
                             routed_scaling=2.5, init_std=0.3, form="relu2")
    x = jax.random.normal(jax.random.PRNGKey(3), (t, d))
    layer.build(jax.random.PRNGKey(4), jax.ShapeDtypeStruct(x.shape, x.dtype))
    params = layer.get_parameters()
    w = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def module(p, x):
        out, state = layer.apply(p, layer.get_state(), x, training=True)
        return jnp.sum(w * out), state["_counters"]

    (got, counters), grads = jax.value_and_grad(module, (0, 1), has_aux=True)(
        params, x)
    want, want_grads = jax.value_and_grad(
        lambda p, x: jnp.sum(w * _dense_relu2(x, p, held, k)), (0, 1))(params, x)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert _rel(a, b) < 3e-5
    assert float(counters["moe_overflow_layers"]) == overflows
    assert float(counters["moe_dropped_pairs"]) == 0.0
    assert float(counters["moe_pairs_local"]) > (16 if overflows else 0)


# the gated form's results at the parent commit (877eba6), on the seeded layer
# below: output sum, one element, and the sums of the gradient's leaves
# (router, shared_in, shared_out, w_down, w_gate, w_up)
GATED_AT_THE_PARENT = {
    None: ["-0x1.4d8b8a0000000p+6", "-0x1.801dc00000000p-6",
           "0x1.5f317c0000000p+9", "-0x1.916a100000000p+11",
           "-0x1.a5787a0000000p+9", "0x1.319c4c0000000p+11",
           "-0x1.3f319e0000000p+9", "-0x1.1d94b40000000p+13"],
    (1, 6): ["-0x1.aeca720000000p+6", "0x1.13d8640000000p-1",
             "-0x1.ae96680000000p+7", "-0x1.a708be0000000p+11",
             "-0x1.2dbf680000000p+10", "-0x1.51ad2e0000000p+11",
             "0x1.6f32ba0000000p+11", "-0x1.b803900000000p+10"],
}


@pytest.mark.parametrize("held", list(GATED_AT_THE_PARENT),
                         ids=["one-pass", "block-loop"])
def test_the_gated_forms_results_are_what_they_were(held):
    """The three-matrix experts of the sparse and the latent families, through
    the one-pass path and the block loop: bit for bit the parent's."""
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 32))
    m = nn.RoutedExperts(8, 12, 2, experts_held=held, scoring="sigmoid",
                         routed_scaling=2.5, bias_update_rate=0.001,
                         shared_size=12, init_std=0.3)
    assert m.form == "gated"
    m.build(jax.random.PRNGKey(1), jax.ShapeDtypeStruct(x.shape, x.dtype))
    p, s = m.get_parameters(), m.get_state()
    assert p["shared_in"].shape == (32, 24) and p["w_gate"].shape[1:] == (32, 12)
    y = m.apply(p, s, x, training=True)[0]
    g = jax.grad(lambda p: jnp.sum(m.apply(p, s, x, training=True)[0] ** 2))(p)
    got = [float(jnp.sum(y)).hex(), float(y[3, 5]).hex()] + [
        float(jnp.sum(v)).hex() for _, v in sorted(g.items())]
    assert got == GATED_AT_THE_PARENT[held]


def test_an_unknown_form_is_refused():
    with pytest.raises(ValueError, match="relu2"):
        nn.RoutedExperts(8, 12, 2, form="gelu")


@pytest.mark.parametrize("size,tile", [
    (1856, 640),    # 14.5 x 128: three tiles overhang it by 64, the largest such
    (2688, 896), (2048, 1024), (768, 768), (2304, 768),   # as before: divisors
    (24, 24), (100, 100),                                 # below one lane group
])
def test_the_grouped_kernels_tile_follows_the_shapes(size, tile):
    assert moe._tile(size) == tile


# ------------------------------------------------------ the mixer's groups

def test_the_mixers_norm_statistic_is_a_groups_own():
    # activations of order one: at a tiny scale eps is the whole statistic
    mixer = nn.Mamba2Mixer(heads=8, head_dim=8, state=16, chunk=8, groups=4,
                           init_std=0.3)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32))
    mixer.build(jax.random.PRNGKey(1), jax.ShapeDtypeStruct(x.shape, x.dtype))
    params = mixer.get_parameters()
    out, _ = mixer.apply(params, mixer.get_state(), x, training=True)
    cfg = dict(mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
               n_groups=4, chunk_size=8, layer_norm_epsilon=1e-5)
    want, _ = ref.mamba(x[0], params, cfg)
    np.testing.assert_allclose(out[0], want, atol=2e-6)
    # over all of d_inner it is another number, and so is group 0 for all heads
    for fault in ("norm_over_all", "scan_group_zero"):
        other, _ = ref.mamba(x[0], params, {**cfg, fault: True})
        assert _rel(other, want) > 1e-2


def test_heads_must_split_into_the_groups():
    with pytest.raises(ValueError, match="B/C groups"):
        nn.Mamba2Mixer(heads=8, head_dim=8, state=16, groups=3)


# --------------------------------------------------------------- from_config

@pytest.mark.parametrize("change,names", [
    ({"hybrid_override_pattern": "EM-EM"}, ["hybrid_override_pattern", "'-'"]),
    ({"hybrid_override_pattern": "EM*E"}, ["hybrid_override_pattern", "5"]),
    ({"n_group": 8}, ["n_group", "1"]),
    ({"topk_group": 4}, ["topk_group", "1"]),
    ({"mamba_proj_bias": True}, ["mamba_proj_bias", "False"]),
    ({"use_conv_bias": False}, ["use_conv_bias", "True"]),
    ({"mlp_hidden_act": "silu"}, ["mlp_hidden_act", "relu2"]),
    ({"tie_word_embeddings": True}, ["tie_word_embeddings", "False"]),
    ({"norm_topk_prob": False}, ["norm_topk_prob", "True"]),
    ({"n_groups": 3}, ["B/C groups"]),
])
def test_from_config_names_what_it_accepts(change, names):
    with pytest.raises(ValueError) as e:
        decoder_lm.from_config({**CONFIG, **change})
    assert all(n in str(e.value) for n in names)


def test_an_experts_layer_takes_no_other_feed_forward():
    kw = dict(vocab_size=64, hidden_size=32, num_heads=4, num_kv_heads=2,
              head_dim=8, n_experts=8, experts_per_token=2, expert_size=12)
    with pytest.raises(ValueError, match="'none'"):
        nn.DecoderLM(layer_types=["experts"], mlp_layer_types=["sparse"], **kw)
    with pytest.raises(ValueError, match="n_experts"):
        nn.DecoderLM(layer_types=["experts"], **{**kw, "n_experts": 0,
                                                 "mlp_size": 8})
    # its default is the one it takes
    model = nn.DecoderLM(layer_types=["experts", "attention"], **kw)
    kinds = [type(m.modules[0]).__name__ for m in model.modules
             if isinstance(m, nn.Remat)]
    assert kinds == ["MixerBlock", "DecoderBlock"]


def test_the_published_config_builds_by_shape_inference_alone():
    """All 52 layers' kinds, 128 experts, the full vocabulary: 31.6B
    parameters counted from shapes, nothing allocated."""
    published = {**CONFIG, **dict(
        vocab_size=131072, hidden_size=2688, num_hidden_layers=52,
        hybrid_override_pattern=PUBLISHED_PATTERN, mamba_num_heads=64,
        mamba_head_dim=64, ssm_state_size=128, n_groups=8, chunk_size=128,
        moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
        n_routed_experts=128, num_experts_per_tok=6, num_attention_heads=32,
        num_key_value_heads=2, head_dim=128)}
    kinds = decoder_lm.layer_types(published)
    assert (kinds.count("mamba"), kinds.count("experts"),
            kinds.count("attention")) == (23, 23, 6)
    model = decoder_lm.from_config(published)
    shapes = jax.eval_shape(
        lambda: (model.build(jax.random.PRNGKey(0),
                             jax.ShapeDtypeStruct((1, 256), jnp.int32)),
                 model.get_parameters())[1])
    assert sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes)) \
        == 31_577_937_344
    assert shapes["layer_1"]["block"]["experts"]["w_up"].shape == (
        128, 2688, 1856)
    assert shapes["layer_0"]["block"]["ssm"]["in_proj"].shape == (2688, 10304)
    assert shapes["layer_5"]["block"]["attn"]["wk"].shape == (2688, 256)


# ------------------------------------------------------------- the two copies

@pytest.mark.parametrize("operands", [None, "bfloat16"])
def test_the_benchmarks_copy_of_the_reference_gives_identical_outputs(
        both, operands):
    path = os.path.join(ROOT, "benchmark", "configs",
                        "nemotron_3_nano_30b_a3b_reference.py")
    spec = importlib.util.spec_from_file_location("bench_nemotron_copy", path)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    rcfg = {**both["rcfg"], "operands": operands}
    at = jnp.asarray([[0, 5, T - 2], [7, 7, 3]])
    args = (both["rparams"], both["rbiases"], both["x"], both["y"], rcfg)
    a = ref.loss_and_grad(*args, at=at)
    b = copy.loss_and_grad(*args, at=at)
    assert a[3].shape == (N, 3, CONFIG["vocab_size"])
    for u, v in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert jnp.array_equal(u, v)
    assert ref.counters(a[2], rcfg, N, T) == copy.counters(b[2], rcfg, N, T)


def test_the_reference_imports_nothing_of_the_program():
    for name in ("bigdl_tpu/models/nemotron_h_lm_reference.py",
                 "benchmark/configs/nemotron_3_nano_30b_a3b_reference.py"):
        with open(os.path.join(ROOT, name)) as f:
            imports = [line for line in f
                       if line.startswith(("import ", "from "))]
        assert not any("bigdl_tpu" in line or "pallas" in line
                       for line in imports)


@pytest.mark.parametrize("fault", [
    "scan_group_zero", "norm_over_all", "gated_expert", "bias_in_weights"])
def test_each_planted_fault_moves_the_references_answer(both, fault):
    """What the benchmark's controls plant: each one changes loss or
    gradients by far more than float32 rounding."""
    l, g, _, _ = ref.loss_and_grad(
        both["rparams"], both["rbiases"], both["x"], both["y"],
        {**both["rcfg"], fault: True})
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


def test_language_model_trains_through_optimize_with_counters_in_the_record():
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.obs import Telemetry
    from bigdl_tpu.optim import Adam, LocalOptimizer
    from bigdl_tpu.optim.trigger import Trigger

    config = {**CONFIG, "experts_held": [0, 1, 5], "initializer_range": 0.02}
    p = 1.0 / np.arange(1, 65)
    tok = np.random.default_rng(0).choice(
        64, size=(16, T + 1), p=p / p.sum()).astype(np.int32)
    data = DataSet.array(tok[:, :-1].copy(), tok[:, 1:].copy(), batch_size=2)
    model = decoder_lm.from_config(config)
    opt = LocalOptimizer(model, data, nn.TokenCrossEntropyCriterion())
    opt.set_optim_method(Adam(learningrate=3e-3, beta1=0.9, beta2=0.95))
    keep = _Keep()
    tel = Telemetry(exporters=[keep])
    opt.set_telemetry(tel)
    opt.set_end_when(Trigger.max_iteration(24))
    opt.optimize()
    tel.close()
    steps = [r for r in keep.records if r.get("type") == "step"]
    assert len(steps) == 24
    assert steps[0]["loss"] == pytest.approx(math.log(64), abs=0.3)
    assert np.median([r["loss"] for r in steps[-8:]]) < np.median(
        [r["loss"] for r in steps[:4]])
    for i, r in enumerate(steps):
        assert r["moe_dropped_pairs"] == 0.0
        assert 0 < r["moe_pairs_local"] <= 2 * N * T * 3
        assert r["moe_load_max_over_mean"] >= 1.0
        assert r["ssm_log_decay_min"] < 0 < r["ssm_state_rms"]
        assert 0.0 < r["moe_bias_abs_max"] <= 0.001 * (i + 1) + 1e-7
    assert steps[-1]["compile_count"] == 1
    compiles = [r for r in keep.records if r.get("type") == "compile"]
    assert sum(r["count"] for r in compiles) == 1
    # how the scans were cut: 4 B/C groups of 2 heads each, in the XLA form
    scan, = compiles[0]["ssd_scans"]
    assert {k: scan[k] for k in ("records", "tokens", "chunk", "chunks", "heads",
                                 "groups", "group_heads", "kernel")} == dict(
        records=2, tokens=32, chunk=8, chunks=4, heads=8, groups=4,
        group_heads=2, kernel=False)
    assert scan["calls"] >= 2     # two mamba layers, traced at least once each
    biases = decoder_lm.reference_biases(model.get_state())
    assert len(biases) == 2
    assert max(float(jnp.max(jnp.abs(b))) for b in biases) == pytest.approx(
        steps[-1]["moe_bias_abs_max"])
