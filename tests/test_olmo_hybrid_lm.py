"""The gated delta-rule hybrid (Olmo-Hybrid-7B's mechanisms: Gated DeltaNet
layers with beta in (0, 2), full attention without rotary behind q/k norms
over the whole projection, Olmo's post-norm blocks) against its plain
float32 reference, at small sizes on the CPU: D 32, layers (linear x 3,
full), 2 linear heads of keys 8 and values 16, 4 attention heads of 8, MLP
48, vocabulary 96, the op's chunks cut to 8 at T 24 (three chunks, the last
ragged at T 21). Beside it: the family's refusals, the post-norm block and the
whole-width q/k norm by hand (their defaults are held to the programs they
built before, in ``tests/test_laguna_lm.py``'s digests)."""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.models import decoder_lm, delta_hybrid_lm_reference as ref
from bigdl_tpu.ops import delta_rule

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = dict(
    model_type="olmo_hybrid", vocab_size=96, hidden_size=32,
    intermediate_size=48, num_hidden_layers=4,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    num_attention_heads=4, num_key_value_heads=4, rms_norm_eps=1e-6,
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None},
    tie_word_embeddings=False, attention_bias=False, hidden_act="silu",
    initializer_range=0.2)
N, T = 2, 24


def _tokens(seed, t=T):
    tok = np.random.default_rng(seed).integers(
        0, CONFIG["vocab_size"], (N, t + 1)).astype(np.int32)
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def _built(config, seed=0, t=T):
    model = decoder_lm.from_config(config)
    model.build(jax.random.PRNGKey(seed),
                jax.ShapeDtypeStruct((N, t), jnp.int32))
    return model, model.get_parameters(), model.get_state()


def _blocks(model):
    return [m.modules[0] for m in model.modules
            if isinstance(m, nn.Remat)]


def _mixers(model, kind):
    return [b.modules[1] for b in _blocks(model)
            if isinstance(b.modules[1], kind)]


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _compare(t):
    model, params, state = _built(CONFIG, t=t)
    x, y = _tokens(1, t)
    criterion = nn.TokenCrossEntropyCriterion()

    def loss(p):
        out, new_state = model.apply(p, state, x, training=True)
        return criterion._apply(out, y), (out, new_state)

    chunks, op = [], delta_rule.delta_rule

    def in_chunks_of_8(*args):
        chunks.append(args[0].shape[1])
        return op(*args, chunk=8)

    # the mixer calls the op with its default chunk (64); a tiny record
    # needs a smaller one to cross chunk boundaries
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(delta_rule, "delta_rule", in_chunks_of_8)
        (value, (logits, new_state)), grads = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(params)
    assert chunks == [t] * 3
    rcfg = decoder_lm.reference_config(CONFIG)
    at = jnp.asarray([[0, 5, t - 2], [7, 7, 3]])
    rloss, rgrads, rbeta, rlogits = ref.loss_and_grad(
        decoder_lm.reference_params(params), x, y, rcfg, block_q=t, at=at,
        segment=t)
    return dict(model=model, params=params, loss=value, logits=logits,
                counters=model.counters_tree(new_state), grads=grads, x=x,
                y=y, at=at, rloss=rloss, rgrads=rgrads, rbeta=rbeta,
                rlogits=rlogits, rcfg=rcfg)


@pytest.fixture(scope="module")
def both():
    return _compare(T)


# ------------------------------------------------------------ the model built

def test_from_config_builds_delta_and_attention_layers_under_post_norm(both):
    model = both["model"]
    assert isinstance(model, nn.DecoderLM)
    blocks = _blocks(model)
    assert all(isinstance(b, nn.DecoderBlock) and b.post_norm for b in blocks)
    delta = _mixers(model, nn.GatedDeltaNet)
    attn = _mixers(model, nn.GroupedQueryAttention)
    assert len(delta) == 3 and len(attn) == 1
    assert [(m.heads, m.key_dim, m.value_dim, m.conv, m.neg_eigval)
            for m in delta] == [(2, 8, 16, 4, True)] * 3
    assert attn[0].qk_norm == "whole" and attn[0]._rope is None
    layer = both["params"]["layer_3"]["block"]["attn"]
    assert layer["q_norm"].shape == (32,) and layer["k_norm"].shape == (32,)
    linear = both["params"]["layer_0"]["block"]["delta"]
    assert linear["conv_w"].shape == (2 * 16 + 32, 4)
    assert "conv_b" not in linear and linear["norm"].shape == (16,)
    assert both["params"]["head"]["weight"].shape == (32, 96)


# --------------------------------------------------------- against the reference

@pytest.mark.parametrize("t", [T, 21], ids=["whole-chunks", "ragged-chunk"])
def test_loss_and_logits_match_the_reference(both, t):
    got = both if t == T else _compare(t)
    assert float(got["loss"]) == pytest.approx(float(got["rloss"]), abs=2e-6)
    rows = jnp.arange(N)[:, None]
    np.testing.assert_allclose(got["logits"][rows, got["at"]],
                               got["rlogits"], atol=2e-5)


LEAVES = ["embed", "head", "final_norm", "0.wq", "0.wk", "0.wv", "0.wz",
          "0.wa", "0.wb", "0.conv_w", "0.A_log", "0.dt_bias", "0.norm",
          "0.wo", "0.ln1", "0.ln2", "0.w_in", "3.wq", "3.q_norm", "3.k_norm",
          "3.wo"]


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(both, leaf):
    got = decoder_lm.reference_params(both["grads"])
    want = both["rgrads"]
    if "." in leaf:
        layer, name = leaf.split(".")
        got, want = got["layers"][int(layer)][name], \
            want["layers"][int(layer)][name]
    else:
        got, want = got[leaf], want[leaf]
    assert got.shape == want.shape
    assert _rel(got, want) < 2e-4


def test_every_gradient_leaf_is_compared(both):
    got = jax.tree_util.tree_leaves(decoder_lm.reference_params(both["grads"]))
    want = jax.tree_util.tree_leaves(both["rgrads"])
    assert len(got) == len(want)
    assert max(_rel(a, b) for a, b in zip(got, want)) < 2e-4


def test_beta_counter_matches_the_reference_and_lies_in_zero_two(both):
    beta = float(both["counters"]["delta_beta_mean"])
    assert beta == pytest.approx(float(both["rbeta"]), abs=1e-6)
    # beta = 2 sigmoid(.): its mean near 1 at small weights, above sigmoid's
    assert 0.5 < beta < 1.5


def test_beta_is_doubled_and_can_exceed_one():
    model = decoder_lm.from_config(CONFIG)
    mixer = _mixers(model, nn.GatedDeltaNet)[0]
    mixer.build(jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, 8, 32),
                                                            jnp.float32))
    params = dict(mixer.get_parameters(), wb=jnp.full((32, 2), 0.5))
    x = jnp.ones((1, 8, 32))
    _, state = mixer.apply(params, mixer.get_state(), x)
    want = 2.0 / (1.0 + math.exp(-16.0))
    assert float(state["_counters"]["delta_beta_mean"]) == pytest.approx(
        want, rel=1e-6)
    single = decoder_lm.from_config({**CONFIG,
                                     "linear_allow_neg_eigval": False})
    assert not _mixers(single, nn.GatedDeltaNet)[0].neg_eigval


# --------------------------------------------------------------- the reference

@pytest.mark.parametrize("operands", [None, "bfloat16"])
def test_the_benchmarks_copy_of_the_reference_gives_identical_outputs(
        both, operands):
    path = os.path.join(ROOT, "benchmark", "configs",
                        "olmo_hybrid_7b_reference.py")
    spec = importlib.util.spec_from_file_location("bench_olmo_copy", path)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    rcfg = {**both["rcfg"], "operands": operands}
    args = (decoder_lm.reference_params(both["params"]), both["x"],
            both["y"], rcfg)
    a = ref.loss_and_grad(*args, block_q=T, at=both["at"], segment=8)
    b = copy.loss_and_grad(*args, block_q=T, at=both["at"], segment=8)
    for u, v in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert jnp.array_equal(u, v)


def test_the_references_segments_give_the_whole_records_answer(both):
    rparams = decoder_lm.reference_params(both["params"])
    a = ref.loss_and_grad(rparams, both["x"], both["y"], both["rcfg"],
                          block_q=8, segment=8)
    assert float(a[0]) == pytest.approx(float(both["rloss"]), abs=1e-6)
    for u, v in zip(jax.tree_util.tree_leaves(a[1]),
                    jax.tree_util.tree_leaves(both["rgrads"])):
        assert _rel(u, v) < 1e-5


@pytest.mark.parametrize("fault", [
    {"linear_allow_neg_eigval": False}, {"qk_l2norm": False},
    {"decay": False}, {"gate_then_norm": True}, {"post_norm": False},
    {"qk_norm_per_head": True}], ids=lambda f: next(iter(f)))
def test_each_planted_fault_moves_the_references_answer(both, fault):
    rparams = decoder_lm.reference_params(both["params"])
    loss, grads, _, _ = ref.loss_and_grad(
        rparams, both["x"], both["y"], {**both["rcfg"], **fault},
        block_q=T, segment=8)
    assert abs(float(loss) - float(both["rloss"])) > 1e-4
    worst = max(_rel(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(grads),
        jax.tree_util.tree_leaves(both["rgrads"])))
    assert worst > 0.05


def test_a_later_token_changes_nothing_before_it(both):
    model = both["model"]
    x = both["x"]
    changed = x.at[:, 15].set((x[:, 15] + 1) % CONFIG["vocab_size"])
    a, _ = model.apply(both["params"], model.get_state(), x)
    b, _ = model.apply(both["params"], model.get_state(), changed)
    np.testing.assert_array_equal(a[:, :15], b[:, :15])
    assert float(jnp.max(jnp.abs(a[:, 15:] - b[:, 15:]))) > 1e-4


# ------------------------------------------------------------- the refusals

@pytest.mark.parametrize("change,names", [
    ({"linear_num_value_heads": 4}, "linear_num_value_heads"),
    ({"rope_parameters": {"rope_theta": 10000}}, "rope_theta"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"attention_bias": True}, "attention_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"layer_types": ["linear_attention", "sliding_attention"] * 2},
     "sliding_attention"),
    ({"mlp_layer_types": ["dense"] * 4}, "mlp_layer_types"),
], ids=["value-heads", "rotary", "tied", "bias", "act", "kind", "mlp-kinds"])
def test_from_config_names_what_it_refuses(change, names):
    with pytest.raises(ValueError, match=names):
        decoder_lm.from_config({**CONFIG, **change})


def test_a_model_refuses_post_norm_beside_a_one_mixer_layer():
    with pytest.raises(ValueError, match="post_norm"):
        nn.DecoderLM(64, 32, ["mamba"], 4, 4, 8, mamba=dict(
            heads=8, head_dim=8, state=16), mlp_size=48,
            mlp_layer_types=["none"], post_norm=True)


def test_an_unknown_qk_norm_is_refused():
    with pytest.raises(ValueError, match="qk_norm"):
        nn.GroupedQueryAttention(4, 4, 8, qk_norm="per_head")


# ------------------------------------------------- the block and the norm

def test_the_whole_width_norm_is_one_statistic_over_every_head():
    attn = nn.GroupedQueryAttention(4, 4, 8, qk_norm="whole")
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 32))
    attn.build(jax.random.PRNGKey(1), jax.ShapeDtypeStruct(x.shape, x.dtype))
    p = attn.get_parameters()
    gain = jnp.linspace(0.5, 1.5, 32)
    q = attn._heads(x, p["wq"], 4, gain)          # (N, H, T, d)
    raw = x @ p["wq"]
    want = raw / jnp.sqrt(jnp.mean(raw * raw, -1, keepdims=True) + 1e-6) * gain
    np.testing.assert_allclose(q.transpose(0, 2, 1, 3).reshape(1, 6, 32),
                               want, rtol=1e-5, atol=1e-6)


class _Mixer(nn.GatedMLP):
    """A gated MLP in the mixer's place (named ``mixer`` in the block)."""


def test_the_post_norm_block_by_hand():
    block = nn.DecoderBlock(_Mixer(24), nn.GatedMLP(16), eps=1e-6,
                            post_norm=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 8))
    block.build(jax.random.PRNGKey(1), jax.ShapeDtypeStruct(x.shape, x.dtype))
    p = block.get_parameters()
    out, _ = block.apply(p, block.get_state(), x)

    def norm(v, g):
        return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + 1e-6) * g

    def gated(v, w):
        a, b = jnp.split(v @ w["w_in"], 2, -1)
        return (jax.nn.silu(a) * b) @ w["w_out"]

    h = x + norm(gated(x, p["mixer"]), p["ln1"]["weight"])
    want = h + norm(gated(h, p["mlp"]), p["ln2"]["weight"])
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


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


def test_language_model_trains_through_optimize_with_the_counter_in_the_record():
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.obs import Telemetry
    from bigdl_tpu.optim import Adam, LocalOptimizer
    from bigdl_tpu.optim.trigger import Trigger

    config = {**CONFIG, "initializer_range": 0.02}
    p = 1.0 / np.arange(1, 97)
    tok = np.random.default_rng(0).choice(
        96, size=(16, T + 1), p=p / p.sum()).astype(np.int32)
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
    assert steps[0]["loss"] == pytest.approx(math.log(96), abs=0.5)
    assert np.median([r["loss"] for r in steps[-8:]]) < np.median(
        [r["loss"] for r in steps[:4]])
    assert all(0.0 < r["delta_beta_mean"] < 2.0 for r in steps)
    assert steps[-1]["compile_count"] == 1
