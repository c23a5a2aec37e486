"""The state-space / attention hybrid language model (nn.Mamba2Mixer,
ops.ssd_scan, nn.GatedMLP, the decoder's four multipliers and tied head)
against its plain float32 reference, at small sizes on the CPU: D 32, the
pattern [mamba, attention, mamba], 8 mamba heads of 8 with state 16 in chunks
of 8, 4 query / 2 K/V heads of 8, MLP 48, vocabulary 96."""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.models import decoder_lm, hybrid_lm_reference as ref
from bigdl_tpu.nn.attention import scaled_dot_product_attention
from bigdl_tpu.ops import ssd
from bigdl_tpu.ops.flash_attention import _dense_reference, flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=3,
    layer_types=["mamba", "attention", "mamba", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, rms_norm_eps=1e-5,
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4,
    mamba_chunk_size=8, mamba_expand=2, mamba_n_groups=1,
    mamba_conv_bias=True, mamba_proj_bias=False, shared_intermediate_size=48,
    num_local_experts=0, attention_multiplier=0.2, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=8,
    position_embedding_type="nope", tie_word_embeddings=True)
N, T = 2, 24


def _tokens(seed, n=N, t=T):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, CONFIG["vocab_size"], (n, t + 1)).astype(np.int32)
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def _built(config, seed=0, t=T):
    model = decoder_lm.from_config(config)
    model.build(jax.random.PRNGKey(seed), jax.ShapeDtypeStruct((N, t), jnp.int32))
    return model, model.get_parameters(), model.get_state()


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _loss_fn(model, state, x, y):
    criterion = nn.TokenCrossEntropyCriterion()

    def loss(p):
        out, new_state = model.apply(p, state, x, training=True)
        return criterion._apply(out, y), (out, new_state)

    return loss


# -------------------------------------------------------------------- the scan

def _scan_inputs(t, h=4, p=8, s=16, n=2, seed=0):
    """Inputs whose decays span one to a thousand tokens: dt A from about -1
    (forgotten within a token) to -0.001 a token, head by head."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (n, t, h, p))
    rate = jnp.exp(jnp.linspace(math.log(1e-3), 0.0, h))     # -dt A, a head
    a = -jnp.exp(jax.random.uniform(ks[1], (h,), minval=0.0, maxval=math.log(16)))
    dt = rate / -a * jnp.exp(0.3 * jax.random.normal(ks[2], (n, t, h)))
    b = jax.random.normal(ks[3], (n, t, s))
    c = jax.random.normal(ks[4], (n, t, s))
    d = 1.0 + 0.1 * jax.random.normal(ks[5], (h,))
    return x, dt, a, b, c, d


SCAN_SHAPES = {"below-one-chunk": (5, 8), "whole-chunks": (32, 8),
               "ragged-last-chunk": (29, 8)}
SCAN_INPUTS = ("x", "dt", "a", "b", "c", "d")


@pytest.fixture(scope="module", params=list(SCAN_SHAPES))
def scans(request):
    t, chunk = SCAN_SHAPES[request.param]
    args = _scan_inputs(t)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    every = tuple(range(len(args)))
    y, stats = ssd.ssd_scan(*args, chunk=chunk)
    return dict(
        args=args, y=y, stats=stats, want=ssd.ssd_sequential(*args),
        grads=jax.grad(lambda *a: jnp.sum(
            w * ssd.ssd_scan(*a, chunk=chunk)[0]), every)(*args),
        rgrads=jax.grad(lambda *a: jnp.sum(
            w * ssd.ssd_sequential(*a)), every)(*args))


def test_chunked_scan_gives_the_sequential_recurrences_values(scans):
    assert scans["y"].dtype == jnp.float32
    np.testing.assert_allclose(scans["y"], scans["want"], atol=5e-6)
    # the decays do span three decades, so the chunk boundary is exercised
    rate = -scans["args"][1] * scans["args"][2]
    assert float(rate.min()) < 3e-3 and float(rate.max()) > 0.3
    assert float(scans["stats"].log_decay_min) < -1.0


@pytest.mark.parametrize("i", range(len(SCAN_INPUTS)), ids=SCAN_INPUTS)
def test_chunked_scan_gradient_matches_the_sequential_recurrences(scans, i):
    got, want = scans["grads"][i], scans["rgrads"][i]
    assert got.shape == want.shape
    assert _rel(got, want) < 2e-5


def test_heads_in_groups_give_what_all_heads_at_once_give(monkeypatch):
    args = _scan_inputs(32, h=8)
    whole, _ = ssd.ssd_scan(*args, chunk=8)
    g_whole = jax.grad(lambda x: jnp.sum(ssd.ssd_scan(x, *args[1:], chunk=8)[0] ** 2))(args[0])
    ssd.take_scan_records()
    # room for two heads' L: 2 records x 4 chunks x 8 x 8 float32 a head
    monkeypatch.setattr(ssd, "_GROUP_BYTES", 2 * 2 * 4 * 8 * 8 * 4)
    grouped, _ = ssd.ssd_scan(*args, chunk=8)
    g_grouped = jax.grad(lambda x: jnp.sum(ssd.ssd_scan(x, *args[1:], chunk=8)[0] ** 2))(args[0])
    np.testing.assert_allclose(grouped, whole, atol=2e-6)
    np.testing.assert_allclose(g_grouped, g_whole, atol=2e-5)
    record, = ssd.take_scan_records()
    assert record == dict(records=2, tokens=32, chunk=8, chunks=4, heads=8,
                          head_dim=8, state=16, groups=1, group_heads=8,
                          kernel=False, head_group=2, calls=2)
    assert ssd.take_scan_records() == []


@pytest.mark.parametrize("records,chunks,heads,chunk,group", [
    (1, 32, 64, 256, 8),      # the benchmark's cell: 64 MiB of L at a time
    (2, 32, 64, 256, 4), (1, 1, 64, 256, 64), (8, 128, 6, 256, 1)])
def test_head_group_follows_the_shapes(records, chunks, heads, chunk, group):
    assert ssd.head_group(records, chunks, heads, chunk) == group


def test_scan_statistics_are_the_chunk_boundary_states_own():
    """``ssm_state_rms``'s sum of squares against the recurrence's states at
    t = 8, 16, 24, 29 (a ragged last chunk ends where the record ends)."""
    x, dt, a, b, c, d = _scan_inputs(29)
    _, stats = ssd.ssd_scan(x, dt, a, b, c, d, chunk=8)
    state = np.zeros((2, 4, 8, 16))
    total = 0.0
    for t in range(29):
        decay = np.exp(np.asarray(dt[:, t] * a))[:, :, None, None]
        state = state * decay + np.asarray(
            (dt[:, t, :, None] * x[:, t])[..., None] * b[:, t, None, None, :])
        if (t + 1) % 8 == 0 or t == 28:
            total += float((state ** 2).sum())
    assert float(stats.state_sq_sum) == pytest.approx(total, rel=1e-5)
    assert stats.state_count == 4 * state.size
    cum = np.cumsum(np.pad(np.asarray(dt * a), ((0, 0), (0, 3), (0, 0)))
                    .reshape(2, 4, 8, 4), axis=2)
    assert float(stats.log_decay_min) == pytest.approx(cum.min(), rel=1e-6)


# ------------------------------------------------------- model against reference

@pytest.fixture(scope="module")
def both():
    """The module's and the reference's loss, logits, gradients and counters
    on the same seeded weights and batch."""
    model, params, state = _built(CONFIG)
    x, y = _tokens(1)
    (l, (logits, new_state)), grads = jax.value_and_grad(
        _loss_fn(model, state, x, y), has_aux=True)(params)
    rcfg = decoder_lm.reference_config(CONFIG)
    rparams = decoder_lm.reference_params(params)
    rl, rgrads, stats, _ = ref.loss_and_grad(rparams, x, y, rcfg)
    rlogits = jnp.stack([ref.forward(rparams, x[i], rcfg)[0] for i in range(N)])
    return dict(model=model, params=params, loss=l, logits=logits,
                state=new_state, grads=decoder_lm.reference_params(grads),
                rloss=rl, rlogits=rlogits, rgrads=rgrads,
                rcounters=ref.scan_counters(stats, rcfg, N, T))


def test_from_config_builds_the_one_decoder_class_for_both_families(both):
    assert type(both["model"]) is nn.DecoderLM
    blocks = [m.modules[0] for m in both["model"].modules[1:-2]]
    assert all(isinstance(m, nn.Remat) for m in both["model"].modules[1:-2])
    assert [type(b.modules[1]).__name__ for b in blocks] == [
        "Mamba2Mixer", "GroupedQueryAttention", "Mamba2Mixer"]
    assert all(isinstance(b.modules[3], nn.GatedMLP) for b in blocks)
    # one leaf for embedding and head, no q/k norm, no fourth layer
    assert both["params"]["head"] == {}
    assert sorted(both["params"]["layer_1"]["block"]["attn"]) == [
        "wk", "wo", "wq", "wv"]
    assert "layer_3" not in both["params"]


def test_module_loss_and_logits_match_the_reference(both):
    assert float(both["loss"]) == pytest.approx(float(both["rloss"]), abs=1e-5)
    np.testing.assert_allclose(both["logits"], both["rlogits"], atol=5e-6)


MAMBA = ("ln1", "in_proj", "conv_w", "conv_b", "A_log", "dt_bias", "D", "norm",
         "out_proj", "ln2", "w_in", "w_out")
ATTENTION = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_in", "w_out")
LEAVES = ["embed", "final_norm"] + [
    f"layers/{i}/{k}" for i, keys in enumerate((MAMBA, ATTENTION, MAMBA))
    for k in keys]


@pytest.mark.parametrize("leaf", LEAVES)
def test_module_gradient_leaf_matches_the_reference(both, leaf):
    got, want = both["grads"], both["rgrads"]
    for key in leaf.split("/"):
        key = int(key) if key.isdigit() else key
        got, want = got[key], want[key]
    assert got.shape == want.shape
    assert float(jnp.linalg.norm(want)) > 0
    assert _rel(got, want) < 2e-5


def test_every_gradient_leaf_is_compared(both):
    paths = {jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(both["rgrads"])}
    assert len(paths) == len(LEAVES) == 34


def test_counters_match_the_references_own_states(both):
    got = {k: float(v) for k, v in
           both["model"].counters_tree(both["state"]).items()}
    assert set(got) == {"ssm_log_decay_min", "ssm_state_rms"}
    want = both["rcounters"]
    assert got["ssm_log_decay_min"] == pytest.approx(
        want["ssm_log_decay_min"], rel=1e-5)
    assert got["ssm_log_decay_min"] < 0
    assert got["ssm_state_rms"] == pytest.approx(want["ssm_state_rms"], rel=1e-4)


def test_counters_tree_takes_the_least_of_a_min():
    state = {"a": {"_counters": {"ssm_log_decay_min": jnp.float32(-3.0)}},
             "b": {"c": {"_counters": {"ssm_log_decay_min": jnp.float32(-7.0),
                                       "ssm_state_rms": jnp.float32(0.5)}}}}
    got = nn.Identity().counters_tree(state)
    assert float(got["ssm_log_decay_min"]) == -7.0
    assert float(got["ssm_state_rms"]) == 0.5


# ------------------------------------------------------------ the pieces by hand

def test_residual_multiplier_by_hand():
    block = nn.DecoderBlock(nn.Identity(), nn.Identity(), eps=1e-5,
                            residual_multiplier=0.22)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 3, 8)),
                    jnp.float32)
    block.build(jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype))
    y, _ = block.apply(block.get_parameters(), block.get_state(), x)
    norm = lambda v: v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5)  # noqa: E731
    h = np.asarray(x) + 0.22 * norm(np.asarray(x))
    np.testing.assert_allclose(y, h + 0.22 * norm(h), rtol=1e-6)
    assert [m.name() for m in block.modules] == ["ln1", "mixer", "ln2", "ffn"]


def test_embedding_multiplier_and_logits_divisor_by_hand():
    sizes = dict(vocab_size=16, hidden_size=8, layer_types=["attention"],
                 num_heads=2, num_kv_heads=1, head_dim=4, mlp_size=12)
    x = jnp.asarray([[1, 5, 9, 2]], jnp.int32)

    def logits(params=None, **kw):
        model = nn.DecoderLM(**sizes, **kw)
        model.build(jax.random.PRNGKey(3), jax.ShapeDtypeStruct(x.shape, x.dtype))
        p = model.get_parameters() if params is None else params(
            model.get_parameters())
        return model.apply(p, model.get_state(), x)[0]

    def scaled(p):
        return {**p, "embed": {"weight": p["embed"]["weight"] * 12.0}}

    plain = logits()
    assert jnp.array_equal(logits(embedding_multiplier=12.0), logits(scaled))
    assert not jnp.allclose(logits(embedding_multiplier=12.0), plain)
    np.testing.assert_allclose(logits(logits_divisor=8.0), plain / 8.0, rtol=1e-6)


def test_attention_scale_no_rope_and_no_qk_norm_by_hand():
    attn = nn.GroupedQueryAttention(4, 2, 8, qk_norm=False, scale=0.2)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 6, 16)),
                    jnp.float32)
    attn.build(jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype))
    p = {k: np.asarray(v, np.float64) for k, v in attn.get_parameters().items()}
    assert sorted(p) == ["wk", "wo", "wq", "wv"]
    y, _ = attn.apply(attn.get_parameters(), {}, x)
    xs = np.asarray(x[0], np.float64)
    q = (xs @ p["wq"]).reshape(6, 4, 8)
    k = (xs @ p["wk"]).reshape(6, 2, 8)
    v = (xs @ p["wv"]).reshape(6, 2, 8)
    out = np.zeros((6, 4, 8))
    for h in range(4):
        s = q[:, h] @ k[:, h // 2].T * 0.2          # no rotation, no norm
        s = np.where(np.tril(np.ones((6, 6), bool)), s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = (w / w.sum(-1, keepdims=True)) @ v[:, h // 2]
    np.testing.assert_allclose(y[0], out.reshape(6, 32) @ p["wo"], atol=1e-6)


def test_dense_attention_default_scale_is_what_it_was():
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 5, 4)), jnp.float32)
               for _ in range(3))
    base = scaled_dot_product_attention(q, k, v, causal=True, impl="dense")
    assert jnp.array_equal(base, scaled_dot_product_attention(
        q, k, v, causal=True, impl="dense", scale=None))
    np.testing.assert_allclose(base, scaled_dot_product_attention(
        q, k, v, causal=True, impl="dense", scale=0.5), rtol=1e-6)
    assert not jnp.allclose(base, scaled_dot_product_attention(
        q, k, v, causal=True, impl="dense", scale=1 / 64))


def test_flash_kernel_at_head_size_64_takes_the_scale():
    """32 / 8 heads of 64 in the benchmark's cell; here 4 over 1, T 256 in
    tiles of 128, scale 1/64 for 1/8."""
    rng = np.random.default_rng(0)
    q, w = (jnp.asarray(rng.standard_normal((1, 4, 256, 64)), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((1, 1, 256, 64)), jnp.float32)
            for _ in range(2))

    def flash(q, k, v):
        return flash_attention(q, k, v, True, scale=1 / 64, block_q=128,
                               block_k=128, interpret=True)

    def dense(q, k, v):
        return _dense_reference(q, k, v, True, 1 / 64)

    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-6)
    assert not jnp.allclose(dense(q, k, v), _dense_reference(q, k, v, True, None),
                            atol=1e-3)
    got = jax.grad(lambda *a: jnp.sum(w * flash(*a)), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(w * dense(*a)), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=2e-5)


def test_gated_mlp_and_conv_by_hand():
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 5, 6)),
                    jnp.float32)
    mlp = nn.GatedMLP(4)
    mlp.build(jax.random.PRNGKey(0), jax.ShapeDtypeStruct(x.shape, x.dtype))
    p = mlp.get_parameters()
    assert p["w_in"].shape == (6, 8) and p["w_out"].shape == (4, 6)
    ab = np.asarray(x) @ np.asarray(p["w_in"])
    a, b = ab[..., :4], ab[..., 4:]
    np.testing.assert_allclose(
        mlp.apply(p, {}, x)[0], (a / (1 + np.exp(-a)) * b) @ np.asarray(p["w_out"]),
        atol=1e-6)
    from bigdl_tpu.nn.ssm import causal_depthwise_conv

    w = jnp.asarray(np.random.default_rng(4).standard_normal((6, 4)), jnp.float32)
    bias = jnp.arange(6, dtype=jnp.float32)
    got = causal_depthwise_conv(x, w, bias)
    want = np.zeros((2, 5, 6))
    for t in range(5):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += np.asarray(x[:, t - 3 + k] * w[:, k])
    np.testing.assert_allclose(got, want + np.asarray(bias), atol=1e-6)
    np.testing.assert_allclose(ref.causal_conv(x[0], w, bias), got[0], atol=1e-6)


def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses(both):
    untied = decoder_lm.from_config({**CONFIG, "tie_word_embeddings": False})
    untied.build(jax.random.PRNGKey(0), jax.ShapeDtypeStruct((N, T), jnp.int32))
    embed = both["params"]["embed"]["weight"]
    params = {**both["params"], "head": {"weight": embed.T}}
    assert untied.get_parameters()["head"]["weight"].shape == embed.T.shape
    x, y = _tokens(1)
    (l, _), g = jax.value_and_grad(
        _loss_fn(untied, untied.get_state(), x, y), has_aux=True)(params)
    assert float(l) == pytest.approx(float(both["loss"]), abs=1e-6)
    both_uses = g["embed"]["weight"] + g["head"]["weight"].T
    assert _rel(both["grads"]["embed"], both_uses) < 1e-6
    assert _rel(g["head"]["weight"].T, both_uses) > 0.01
    assert _rel(g["embed"]["weight"], both_uses) > 0.01


@pytest.mark.parametrize("changed", [5, 8, 13, 23])
def test_a_later_token_changes_nothing_before_it(both, changed):
    """Through conv, scan (inside a chunk, at its first token, across a
    boundary) and attention: bit-identical, not close."""
    model, params = both["model"], both["params"]
    x, _ = _tokens(1)
    other = x.at[:, changed].set((x[:, changed] + 1) % CONFIG["vocab_size"])
    a = model.apply(params, model.get_state(), x)[0]
    b = model.apply(params, model.get_state(), other)[0]
    assert jnp.array_equal(a[:, :changed], b[:, :changed])
    assert not jnp.allclose(a[:, changed:], b[:, changed:])


def test_the_eight_vocabulary_slices_put_together_are_the_uncut_head(both):
    """One chip's share of a vocabulary split 8 ways is a smaller tied
    vocabulary: the slices' logits side by side are the uncut head's, and
    their log-sum-exps combine to the uncut loss."""
    model, params = both["model"], both["params"]
    x, y = _tokens(1)
    seen = {}
    handle = model.modules[-2].register_forward_hook(
        lambda m, inp, out: seen.update(h=out))
    logits = model.apply(params, model.get_state(), x)[0]
    handle.remove()
    embed = params["embed"]["weight"]
    share = nn.LMHead(12, tied=True, divisor=CONFIG["logits_scaling"])
    slices = [share.apply({"weight": embed[12 * s:12 * (s + 1)].T}, {},
                          seen["h"])[0] for s in range(8)]
    np.testing.assert_allclose(jnp.concatenate(slices, axis=-1), logits,
                               atol=1e-6)
    lse = jax.nn.logsumexp(jnp.stack(
        [jax.nn.logsumexp(s, axis=-1) for s in slices]), axis=0)
    picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    assert float(jnp.mean(lse - picked)) == pytest.approx(
        float(both["loss"]), abs=1e-6)


def test_gradient_accumulators_are_made_when_first_asked_for():
    """772M parameters' worth of zeros beside the parameters, Adam's state
    and the step's temporaries did not fit the chip; only the stateful API
    reads them."""
    layer = nn.Linear(4, 3)
    layer.build(jax.random.PRNGKey(0), jax.ShapeDtypeStruct((2, 4), jnp.float32))
    assert layer.__dict__["_grads_made"] is None
    grads = layer.get_grad_parameters()
    assert jax.tree_util.tree_map(jnp.shape, grads) == jax.tree_util.tree_map(
        jnp.shape, layer.get_parameters())
    assert all(not a.any() for a in jax.tree_util.tree_leaves(grads))
    assert layer.__dict__["_grads_made"] is grads
    x = jnp.ones((2, 4))
    layer.forward(x)
    layer.backward(x, jnp.ones((2, 3)))
    assert any(a.any() for a in jax.tree_util.tree_leaves(
        layer.get_grad_parameters()))
    model, _, _ = _built(CONFIG)
    assert all(m.__dict__["_grads_made"] in (None, {}) for m in model.walk())
    w, g = model.parameters()
    assert [a.shape for a in w] == [a.shape for a in g]


def test_importing_the_layers_imports_no_kernel_package():
    """``import bigdl_tpu.nn`` is in every program's set-up; the kernels'
    package (Pallas, 1.5 s) is owed only by a model that runs one."""
    import subprocess
    import sys

    code = ("import sys, bigdl_tpu.nn, bigdl_tpu.models.decoder_lm; "
            "bad = [m for m in sys.modules if m.startswith('bigdl_tpu.ops') "
            "or 'pallas' in m]; assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)


# ------------------------------------------------------------------ from_config

@pytest.mark.parametrize("change,names", [
    ({"layer_types": ["mamba", "linear_attention", "mamba"]},
     ["linear_attention", "sliding_attention", "mamba"]),
    ({"mlp_layer_types": ["dense", "sparse", "sparse"]}, ["dense", "sparse"]),
    ({"num_local_experts": 4}, ["num_local_experts", "0"]),
    ({"position_embedding_type": "rope"}, ["rope", "nope"]),
    ({"mamba_n_groups": 8}, ["mamba_n_groups", "1"]),
    ({"mamba_expand": 3}, ["mamba_expand"]),
])
def test_from_config_names_what_it_accepts(change, names):
    with pytest.raises(ValueError) as e:
        decoder_lm.from_config({**CONFIG, **change})
    assert all(n in str(e.value) for n in names)


def test_head_dim_defaults_to_the_hidden_size_over_the_heads():
    assert decoder_lm.head_dim(CONFIG) == 8
    assert decoder_lm.head_dim({**CONFIG, "head_dim": 16}) == 16
    assert decoder_lm.reference_config(CONFIG)["head_dim"] == 8


# ------------------------------------------------------- the benchmark's copy

@pytest.mark.parametrize("operands", [None, "bfloat16"])
def test_the_benchmarks_copy_of_the_reference_gives_identical_outputs(operands):
    path = os.path.join(ROOT, "benchmark", "configs",
                        "granite_4_0_h_micro_reference.py")
    spec = importlib.util.spec_from_file_location("bench_hybrid_copy", path)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    _, params, _ = _built(CONFIG, seed=3)
    x, y = _tokens(4)
    rcfg = {**decoder_lm.reference_config(CONFIG), "operands": operands}
    rparams = decoder_lm.reference_params(params)
    at = jnp.asarray([[0, 5, 23], [7, 7, 20]])
    a = ref.loss_and_grad(rparams, x, y, rcfg, at=at)
    b = copy.loss_and_grad(rparams, x, y, rcfg, at=at)
    assert a[3].shape == (N, 3, CONFIG["vocab_size"])
    leaves = jax.tree_util.tree_leaves(a)
    assert len(leaves) == 1 + 34 + 2 + 1
    for u, v in zip(leaves, jax.tree_util.tree_leaves(b)):
        assert jnp.array_equal(u, v)
    assert jnp.array_equal(ref.forward(rparams, x[0], rcfg)[0],
                           copy.forward(rparams, x[0], rcfg)[0])
    assert ref.scan_counters(a[2], rcfg, N, T) == copy.scan_counters(
        b[2], rcfg, N, T)


def test_reference_at_bfloat16_operands_leaves_the_recurrence_in_float32():
    _, params, _ = _built(CONFIG, seed=5)
    lp = decoder_lm.reference_params(params)["layers"][0]
    rcfg = decoder_lm.reference_config(CONFIG)
    h = jnp.asarray(np.random.default_rng(7).standard_normal((T, 32)),
                    jnp.float32)
    plain, stats = ref.mamba(h, lp, rcfg)
    rounded, rstats = ref.mamba(h, lp, {**rcfg, "operands": "bfloat16"})
    assert 1e-4 < _rel(rounded, plain) < 2e-2
    # the recurrence itself rounds nothing: fed the same float32 inputs it
    # is the same, whatever the operands say
    args = _scan_inputs(T)
    y, ends = ref.recurrence(*(a[0] for a in args[:2]), args[2],
                             args[3][0], args[4][0], args[5], 8)
    np.testing.assert_allclose(y, ssd.ssd_sequential(*args)[0], atol=1e-6)
    assert ends.shape == (3, 4, 8, 16)


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


def _optimize(config, steps, seed=None, rate=3e-3):
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.obs import Telemetry
    from bigdl_tpu.optim import Adam, LocalOptimizer
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.utils.random import RandomGenerator

    if seed is not None:
        RandomGenerator.set_seed(seed)
    v = config["vocab_size"]
    p = 1.0 / np.arange(1, v + 1)
    tok = np.random.default_rng(0).choice(
        v, size=(16, 33), p=p / p.sum()).astype(np.int32)
    data = DataSet.array(tok[:, :-1].copy(), tok[:, 1:].copy(), batch_size=2)
    opt = LocalOptimizer(decoder_lm.from_config(config), data,
                         nn.TokenCrossEntropyCriterion())
    opt.set_optim_method(Adam(learningrate=rate, beta1=0.9, beta2=0.95))
    keep = _Keep()
    tel = Telemetry(exporters=[keep])
    opt.set_telemetry(tel)
    opt.set_end_when(Trigger.max_iteration(steps))
    opt.optimize()
    tel.close()
    return keep.records


def test_hybrid_trains_through_optimize_with_counters_in_the_record():
    records = _optimize(CONFIG, 24)
    steps = [r for r in records if r.get("type") == "step"]
    assert len(steps) == 24
    assert steps[0]["loss"] == pytest.approx(math.log(96), abs=0.5)
    assert np.median([r["loss"] for r in steps[-8:]]) < np.median(
        [r["loss"] for r in steps[:4]])
    for r in steps:
        assert r["ssm_log_decay_min"] < 0
        assert r["ssm_state_rms"] > 0
        assert "moe_pairs_local" not in r
    assert steps[-1]["compile_count"] == 1
    compiles = [r for r in records if r.get("type") == "compile"]
    assert sum(r["count"] for r in compiles) == 1
    # how the scans were cut: 32 tokens in 4 chunks of 8, all 8 heads at once,
    # in the XLA form (the CPU backend takes no kernel)
    scan, = compiles[0]["ssd_scans"]
    assert {k: scan[k] for k in ("records", "tokens", "chunk", "chunks", "heads",
                                 "kernel", "head_group")} == dict(
        records=2, tokens=32, chunk=8, chunks=4, heads=8, kernel=False,
        head_group=8)
    assert scan["calls"] >= 2     # two mamba layers, traced at least once each


# M's first six losses through optimize() at the parent commit (472d996,
# tests/test_decoder_lm.py's sizes, 4 layers, experts 0-3 held, seed 7):
# the decoder's generalisation leaves its program what it was
PARENT_LOSSES = ["0x1.38ae000000000p+2", "0x1.3426f00000000p+2",
                 "0x1.28443c0000000p+2", "0x1.1f7a440000000p+2",
                 "0x1.1c01420000000p+2", "0x1.1a23200000000p+2"]


@pytest.fixture(scope="module")
def sparse_losses():
    yarn = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}
    config = dict(
        vocab_size=128, hidden_size=64, num_hidden_layers=4,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        sliding_window=8,
        rope_parameters={"full_attention": yarn, "sliding_attention": {
            "rope_type": "default", "rope_theta": 500000}},
        rms_norm_eps=1e-6, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, norm_topk_prob=True,
        experts_held=[0, 1, 2, 3])
    return [r["loss"] for r in _optimize(config, 6, seed=7)
            if r.get("type") == "step"]


@pytest.mark.parametrize("step", range(6))
def test_the_sparse_models_first_losses_are_bit_identical_to_the_parents(
        sparse_losses, step):
    assert float(sparse_losses[step]).hex() == PARENT_LOSSES[step]
