"""The gated delta-rule hybrid's cell on the CPU: its driver end to end at a
tiny fixture configuration (the comparison with the reference included),
the held and the published parameter counts reckoned from the JSON and
counted in the built tree, the counting form and ``attention_cost`` /
``delta_cost`` pinned at the cell's sizes against hand numbers, the
lower-precision control and the six planted faults through ``compare`` at
hidden 128, and the new readers on a rehearsed table of scope times.
Per-layer entries are looked up by name, never by position."""

import inspect
import json
import os
import time

import pytest

from benchmark import run as bench
from benchmark.lib import flops, scope_times, trace

ROOT = bench.ROOT
FIXTURES = os.path.join(bench.HERE, "tests", "fixtures")
TINY = "tiny_olmo.tiny_packed"
CELL = "olmo_hybrid_7b.packed8k"
COMPARED = {"loss_abs", "logits_abs", "grad_rel_l2_head",
            "grad_rel_l2_first_wb", "grad_rel_l2_first_A_log",
            "grad_rel_l2_first_wk", "grad_rel_l2_first_norm",
            "grad_rel_l2_full_wq", "grad_rel_l2_full_q_norm",
            "grad_rel_l2_worst", "beta_mean_abs", "update_rel_l2"}
NEW = ["delta_scan_ms.train", "delta_proj_ms.train", "delta_conv_ms.train",
       "delta_scan_roofline.train", "delta_beta_mean.train"]
DELTA = ("delta_proj", "delta_conv", "delta_scan")
APPENDED = {"device_step_ms.train", "attn_roofline.train",
            "attn_full_ms.train", "mlp_ms.train", "input_wait_ms.train",
            "prefetch_place_ms.train", "ring_wait_ms.train",
            "ring_wait_ms_p95.train", "host_step_ms.train",
            "step_call_ms.train", "model_sync_ms.train"}


def _rehearse(trace_on, capsys, **extra):
    rehearsal = {"platform": "cpu", **extra}
    result = bench.run_cell(TINY, 2**31 + 79, 1.0, trace_on,
                            t0=time.perf_counter(),
                            roots=(FIXTURES, bench.HERE), rehearsal=rehearsal)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return result, lines


def _declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cells_files_load_by_name():
    parts = bench.load_cell(CELL)
    assert parts["cell"]["driver"] == "train_ref" and parts["cell"]["chips"] == 1
    assert parts["cell"]["traffic"] == "packed8k"
    assert parts["mix"]["generator"] == "token_records"
    names = [r.NAME for r in parts["readers"]]
    assert names == parts["cell"]["per_layer"] and len(names) == 21
    declared = _declared()
    cell = next(w for w in declared["workloads"] if w["name"] == CELL)
    assert cell["why"] == parts["cell"]["why"] and len(cell["why"]) <= 200
    assert cell["chips"] == 1 and cell["traffic"] == "packed8k"
    assert cell["config"] == "olmo_hybrid_7b" == parts["cell"]["config"]
    by_name = {m["name"]: m for m in declared["per_layer"]}
    for name in names:
        assert CELL in by_name[name].get("workloads", [CELL]), name
    for m in declared["per_layer"]:
        assert (m["name"] in names) == (CELL in m.get("workloads", [CELL])), \
            m["name"]
    reader = {r.NAME: r for r in parts["readers"]}
    for name in names:
        r, m = reader[name], by_name[name]
        assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"]), name
    # the new entries name this cell alone; the accepted ones it reports
    # name it after what they had (by name and membership, so that a later
    # cell appended after it leaves this test as it is)
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
    for name in APPENDED:
        listed = by_name[name]["workloads"]
        assert CELL in listed and listed.index(CELL) > 0, name
    assert {m["name"] for m in declared["per_layer"]
            if CELL in m.get("workloads", ())} == APPENDED | set(NEW)


def test_untraced_run_is_correct_and_compares_with_the_reference(capsys):
    result, lines = _rehearse(False, capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 4
    assert set(result["metrics"]) == {
        "setup_s", "train_records_per_s_per_chip", "train_step_ms_p95"}
    compared = next(ln for ln in lines if "reference_comparison" in ln)
    assert compared["broken"] == []
    assert set(compared["reference_comparison"]) == COMPARED
    for got in compared["reference_comparison"].values():
        assert got["value"] <= got["limit"]
    assert 0.5 < compared["counters"]["delta_beta_mean"] < 1.5
    assert compared["counters"]["delta_beta_mean"] == pytest.approx(
        compared["reference_counters"]["delta_beta_mean"], abs=1e-6)
    assert any(ln.get("agrees") is True for ln in lines)


def _hidden_128(parts):
    """What the limits rest on, at a size the CPU can do: hidden 128, T 256
    (four chunks of 64), bfloat16 operands stated, 4 linear heads of keys 32
    and values 64, 4 attention heads of 32, N(0, 0.02) weights as the
    cell's."""
    cfg = json.loads(json.dumps(parts["cfg"]))
    cfg.update(hidden_size=128, intermediate_size=256, num_attention_heads=4,
               num_key_value_heads=4, linear_num_key_heads=4,
               linear_num_value_heads=4, linear_key_head_dim=32,
               linear_value_head_dim=64, vocab_size=512,
               initializer_range=0.02)
    cfg["dtypes"] = {"compute": "bfloat16", "activation": "float32"}
    cfg["deployment"]["record_tokens"] = 256
    # the system reads (seed 2**31 + 5) loss 7.5e-5, logits 0.067, head
    # 0.038, the first layer's wb 0.068, A_log 0.12, wk 0.068, norm 0.064,
    # the full layer's wq 0.070, q_norm 0.072, worst 0.12, beta 1.0e-5,
    # update 0.28; the control (bfloat16 throughout) 7.4e-4, 0.31, 0.20,
    # 0.32, 0.32, 0.37, 0.28, 0.35, 0.31, 0.43, 2.3e-3, 0.63: every limit
    # but the update's tells the two apart here (the update's tells the
    # system from a step that changes nothing, 1)
    cfg["correct"]["reference"] = {
        "loss_abs": 2.5e-4, "logits_abs": 0.11, "grad_rel_l2_head": 0.06,
        "grad_rel_l2_first_wb": 0.11, "grad_rel_l2_first_A_log": 0.2,
        "grad_rel_l2_first_wk": 0.11, "grad_rel_l2_first_norm": 0.11,
        "grad_rel_l2_full_wq": 0.11, "grad_rel_l2_full_q_norm": 0.11,
        "grad_rel_l2_worst": 0.2, "beta_mean_abs": 1e-4,
        "update_rel_l2": 0.65}
    return cfg, {**parts["mix"], "record_tokens": 256}


STAND_INS = [
    ("system", None), ("lower", {"dtype": "bfloat16"}),
    ("beta_not_doubled", {"linear_allow_neg_eigval": False}),
    ("qk_not_l2_normed", {"qk_l2norm": False}),
    ("no_decay", {"decay": False}),
    ("gate_then_norm", {"gate_then_norm": True}),
    ("pre_norm", {"post_norm": False}),
    ("per_head_qk_norm", {"qk_norm_per_head": True})]


@pytest.fixture(scope="module")
def readings():
    """``compare`` at hidden 128: the system, the lower-precision control and
    the six planted faults in its place."""
    parts = bench.load_cell(TINY, roots=(FIXTURES, bench.HERE))
    cfg, mix = _hidden_128(parts)
    out = {}
    for name, stand_in in STAND_INS:
        logged = []
        agrees = parts["config_module"].compare(
            cfg, mix, parts["generator"], 2**31 + 5,
            lambda **kw: logged.append(kw), block_q=128, stand_in=stand_in)
        assert logged[-1]["reference_operands"] == "bfloat16"
        assert logged[-1]["stand_in"] == stand_in
        out[name] = (agrees, logged[-1])
    return out


def test_the_system_at_the_stated_precision_is_correct(readings):
    agrees, log = readings["system"]
    assert agrees is True and log["broken"] == []
    assert set(log["reference_comparison"]) == COMPARED


@pytest.mark.parametrize("name", [n for n, _ in STAND_INS[1:]])
def test_a_lower_precision_or_a_fault_in_the_systems_place_is_not_correct(
        readings, name):
    agrees, log = readings[name]
    assert agrees is False and log["broken"]
    system = readings["system"][1]["reference_comparison"]
    for k in log["broken"]:
        assert log["reference_comparison"][k]["value"] > 2 * system[k]["value"]


def test_a_step_that_leaves_the_parameters_unchanged_is_not_correct(
        monkeypatch):
    """The optimizer's update planted to hand the parameters back as they
    were: the compiled step runs, its loss and gradients are sound, and the
    parameters' change reads 1 against plain Adam's."""
    from bigdl_tpu.optim import optim_method

    monkeypatch.setattr(optim_method.Adam, "update",
                        lambda self, grads, params, slots, lr, step:
                        (params, slots))
    parts = bench.load_cell(TINY, roots=(FIXTURES, bench.HERE))
    logged = []
    agrees = parts["config_module"].compare(
        parts["cfg"], parts["mix"], parts["generator"], 2**31 + 5,
        lambda **kw: logged.append(kw), block_q=128)
    assert agrees is False and logged[-1]["broken"] == ["update_rel_l2"]
    assert logged[-1]["reference_comparison"]["update_rel_l2"]["value"] == 1.0


def test_the_planted_faults_are_the_modules_own_list():
    mod = bench.load_cell(CELL)["config_module"]
    assert list(mod.FAULTS) == [s for _, s in STAND_INS[2:]]


def test_a_tighter_limit_breaks_correct(capsys, tmp_path):
    with open(os.path.join(FIXTURES, "configs", "tiny_olmo.json")) as f:
        cfg = json.load(f)
    cfg["correct"]["reference"]["logits_abs"] = 0.0
    os.makedirs(tmp_path / "configs")
    (tmp_path / "configs" / "tiny_olmo.json").write_text(json.dumps(cfg))
    result = bench.run_cell(TINY, 11, 1.0, False, t0=time.perf_counter(),
                            roots=(str(tmp_path), FIXTURES, bench.HERE),
                            rehearsal={"platform": "cpu"})
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert result["correct"] is False
    assert next(ln for ln in lines if "broken" in ln and "agrees" not in ln
                and "reference_comparison" in ln)["broken"] == ["logits_abs"]


def test_traced_run_reports_what_a_cpu_trace_allows(capsys):
    r3 = trace.read_chrome_trace(os.path.join(
        ROOT, "bench_artifacts", "resnet50_b128_bf16act_s2d_trace.json.gz"))
    result, _ = _rehearse(True, capsys, device_kind="TPU v5 lite",
                          reduced=trace.reduce_events(r3))
    # the CPU's trace has no device plane, so the scope readers find nothing
    # and leave their metrics out without raising; the host metrics and the
    # counter's reader are there
    assert {"dispatch_ms.train", "compile_first_dispatch_s",
            "model_flops_util_pct.train", "device_idle_pct.train",
            "peak_hbm_gib", "delta_beta_mean.train"} <= set(result["metrics"])
    assert not {"delta_scan_ms.train", "delta_proj_ms.train",
                "delta_conv_ms.train", "delta_scan_roofline.train",
                "attn_full_ms.train", "mlp_ms.train",
                "attn_roofline.train"} & set(result["metrics"])
    assert 0.5 < result["metrics"]["delta_beta_mean.train"]["value"] < 1.5
    assert result["metrics"]["model_flops_util_pct.train"]["value"] > 0


class _Run:
    """What a reader sees of a run, over a rehearsed table of scope times."""

    def __init__(self, seconds, steps=()):
        from benchmark.lib import scopes
        from benchmark.lib.peaks import peaks

        self.peaks = peaks("TPU v5 lite")
        self.logged = []
        self.log = lambda **kw: self.logged.append(kw)
        self.trace_dir = None
        self.steps = self.traced_steps = list(steps)
        only = lambda *names: scopes.ScopeTimes(  # noqa: E731
            6, {k: v for k, v in seconds.items() if k in names}, {})
        self._named_scope_times = {scope_times.HYBRID_SCOPES:
                                   only(*scope_times.HYBRID_SCOPES),
                                   DELTA: only(*DELTA)}
        self._scope_times = only("attn_full", "attn_proj", "lm_head")


def test_every_scope_reader_prints_on_a_rehearsed_trace():
    """The new readers and the accepted scope readers over a table of device
    seconds by scope, as a chip's trace would give: each prints, and the
    rooflines are the hand numbers over their scopes' times."""
    parts = bench.load_cell(CELL)
    cfg, mod = parts["cfg"], parts["config_module"]
    seconds = {"attn_full": 0.030, "attn_proj": 0.020, "mlp": 0.150,
               "delta_proj": 0.110, "delta_conv": 0.012, "delta_scan": 0.060,
               "lm_head": 0.030}
    run = _Run(seconds, [{"delta_beta_mean": 1.02}] * 6)
    run.forward = lambda: None
    run.forward.attention_cost = lambda kind: mod.attention_cost(cfg, 1, kind)
    run.forward.delta_cost = lambda: mod.delta_cost(cfg, 1)
    run.forward.layer_kinds = mod._layer_kinds(cfg)
    got = {r.NAME: r.read(run) for r in parts["readers"]
           if r.NAME.startswith(("delta_", "attn_", "mlp_"))}
    assert got["delta_scan_ms.train"] == pytest.approx(60.0)
    assert got["delta_proj_ms.train"] == pytest.approx(110.0)
    assert got["delta_conv_ms.train"] == pytest.approx(12.0)
    assert got["delta_beta_mean.train"] == pytest.approx(1.02)
    assert got["attn_full_ms.train"] == pytest.approx(30.0)
    assert got["mlp_ms.train"] == pytest.approx(150.0)
    # three linear layers of 37.73 GFLOP each, 3 x 3 x 37.73 / 197 TFLOP/s =
    # 1.72 ms against the bytes' 3 x 3 x 0.379 GB / 819 GB/s = 4.17 ms:
    # memory bound, 4.17 of 60
    flops_, nbytes = mod.delta_cost(cfg, 1)
    assert got["delta_scan_roofline.train"] == pytest.approx(
        100 * 3 * 3 * nbytes / 819e9 / 0.060, rel=1e-9)
    assert any(ln.get("delta_scan_roofline_bound") == "memory"
               for ln in run.logged)
    # one full layer at 30 heads of 128: 3 x 0.5154 TFLOP / 197 = 7.85 ms
    assert got["attn_roofline.train"] == pytest.approx(
        100 * 3 * (4.0 * 128 * 30 * 8192 * 8193 // 2 / 197e12) / 0.030,
        rel=1e-9)
    assert all(0 < got[k] < 100 for k in got if k.endswith("roofline.train"))


def test_the_new_readers_leave_their_metrics_out_where_the_program_has_none():
    """The parent's program has no ``delta_*`` scope and no such counter."""
    parts = bench.load_cell(CELL)
    run = _Run({"attn_full": 0.03, "attn_proj": 0.1, "mlp": 0.1}, [{}] * 3)
    run.forward = lambda: None
    run.forward.layer_kinds = []
    for r in parts["readers"]:
        if r.NAME in NEW:
            assert r.read(run) is None, r.NAME


def test_counting_form_and_costs_against_hand_numbers():
    parts = bench.load_cell(CELL)
    cfg, mod = parts["cfg"], parts["config_module"]
    assert cfg["deployment"]["batch_per_chip"] == 1
    assert mod._layer_kinds(cfg) == ["linear_attention"] * 3 + [
        "full_attention"]
    prods = mod.products(cfg, 1)
    total = sum(2.0 * m * k * n for _, m, k, n in prods)
    by = lambda *keys: sum(  # noqa: E731
        2.0 * m * k * n for name, m, k, n in prods
        if name.split(".")[-1] in keys)
    tokens, d = 8192, 3840
    # a linear layer's projections: q, k (2880), v, z, o (5760), a, b (30)
    assert by("wz", "wa", "wb") / tokens == 3 * 2 * d * (5760 + 30 + 30)
    assert by("wq", "wk", "wv", "wo") / tokens == 2 * d * (
        3 * (2 * 2880 + 5760 + 5760) + 4 * 3840)
    # the delta rule: 128 chunks of 64, 2080 pairs on and below a chunk's
    # diagonal, 2016 below; 30 heads of keys 96 and values 192
    pairs, below, rows = 30 * 128 * 2080, 30 * 128 * 2016, 30 * 8192
    assert by("kk") == 3 * 2 * below * 96
    assert by("qk") == 3 * 2 * pairs * 96 + 2 * 128 * 30 * (8192 * 8193 // 2)
    assert by("solve") == 3 * 2 * below * (96 + 192)
    assert by("ws", "qs", "state") == 3 * 3 * 2 * rows * 96 * 192
    assert by("intra") == 3 * 2 * pairs * 192
    flops_, nbytes = mod.delta_cost(cfg, 1)
    assert flops_ == (2 * below * 96 + 2 * pairs * 96 + 2 * below * 288
                      + 3 * 2 * rows * 96 * 192 + 2 * pairs * 192)
    assert flops_ == pytest.approx(37.73e9, rel=1e-3)
    assert nbytes == 8192 * 30 * (2 * 96 * 2 + 192 * 2 + 2 * 4 + 192 * 4)
    assert by("mlp_in", "mlp_out") / tokens == 4 * 2 * 3 * d * 11008
    assert by("head") == 2.0 * tokens * d * 12544
    # the walk over the counting form's jaxpr counts the same
    import jax
    import jax.numpy as jnp

    shapes = [(m, k, n) for _, m, k, n in prods]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for m, k, n in shapes for s in ((m, k), (k, n))]
    costs = flops.matmul_costs(
        lambda *a: [jnp.dot(x, w) for x, w in zip(a[::2], a[1::2])], *args)
    assert sum(c.flops for c in costs) == pytest.approx(total, rel=1e-9)
    # the delta rule is under 1 % of a forward pass of 15 TFLOP
    assert total == pytest.approx(15.07e12, rel=2e-3)
    assert 3 * flops_ / total < 0.01
    full, fbytes = mod.attention_cost(cfg, 1, "full_attention")
    assert full == 4.0 * 128 * 30 * (8192 * 8193 // 2)
    assert fbytes == 8192 * 128 * 2 * (30 + 30) * 2
    assert mod.attention_cost(cfg, 1, "linear_attention") == (0.0, 0.0)


def test_configuration_keeps_every_published_width():
    with open(os.path.join(FIXTURES, "catalog", "olmo_hybrid_7b.json")) as f:
        row = json.load(f)
    assert row["name"] == "Olmo-Hybrid-7B"
    cfg = bench.load_json("configs", "olmo_hybrid_7b", (bench.HERE,))
    changed = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert changed == set(cfg["reduced"]) == set(cfg["reduced_how"]) == {
        "num_hidden_layers", "vocab_size"}
    declared = next(c for c in _declared()["configs"]
                    if c["name"] == "olmo_hybrid_7b")
    assert declared["reduced"] == cfg["reduced"]
    assert declared["source"] == cfg["source"] == row["source_url"]
    assert declared["file"] == "benchmark/configs/olmo_hybrid_7b.json"
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_hidden_layers"] == 4 < cfg["published"]["num_hidden_layers"]
    assert cfg["deployment"]["pipeline_stages"] == 8
    # reckoned from the JSON's own keys
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    k_conv = cfg["linear_conv_kernel_dim"]
    mlp = 3 * d * f
    linear = (2 * d * h * dk + 3 * d * h * dv + 2 * d * h
              + k_conv * (2 * h * dk + h * dv) + 2 * h + dv + mlp + 2 * d)
    full = 4 * d * d + 2 * d + mlp + 2 * d
    held, published = cfg["held"], cfg["published"]
    assert linear == held["parameters_linear_layer"] \
        == published["parameters_linear_layer"] == 215_570_172
    assert full == held["parameters_full_layer"] \
        == published["parameters_full_layer"] == 185_809_920
    assert held["parameters"] == 3 * linear + full + 2 * v * d + d \
        == 928_862_196
    assert held["bytes_at_16_per_parameter"] == 16 * held["parameters"]
    assert published["parameters"] == 24 * linear + 8 * full \
        + 2 * published["vocab_size"] * d + d == 7_430_870_688
    for key in ("linear_attention", "neg_eigval", "value_heads", "scale",
                "full_attention", "block", "initialisation", "optimizer",
                "residual_stream", "records", "records_per_step", "chunk"):
        assert key in cfg["assumed"], key
    assert set(cfg["correct"]["reference"]) - {"why"} == COMPARED
    # and the program's own parameter tree holds exactly that many numbers,
    # at the published widths
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.models import decoder_lm

    model = decoder_lm.from_config(cfg)
    shapes = jax.eval_shape(
        lambda: (model.build(jax.random.PRNGKey(0), jax.ShapeDtypeStruct(
            (1, 256), jnp.int32)), model.get_parameters())[1])
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) == \
        held["parameters"] == 928_862_196
    layer = lambda i, part: shapes[f"layer_{i}"]["block"][part]  # noqa: E731
    assert layer(0, "delta")["wq"].shape == (3840, 30 * 96)
    assert layer(0, "delta")["wv"].shape == (3840, 30 * 192)
    assert layer(0, "delta")["conv_w"].shape == (11520, 4)
    assert layer(0, "delta")["norm"].shape == (192,)
    assert layer(3, "attn")["q_norm"].shape == (3840,)
    assert layer(3, "mlp")["w_in"].shape == (3840, 2 * 11008)
    assert shapes["head"]["weight"].shape == (3840, 12544)
    blocks = [m.modules[0] for m in model.modules if isinstance(m, nn.Remat)]
    assert all(b.post_norm for b in blocks)
    assert [type(b.modules[1]).__name__ for b in blocks] == [
        "GatedDeltaNet"] * 3 + ["GroupedQueryAttention"]
    from bigdl_tpu.ops.delta_rule import delta_rule

    assert inspect.signature(delta_rule).parameters["chunk"].default \
        == 64 == mod_chunk()


def mod_chunk():
    return bench.load_module("configs", "olmo_hybrid_7b", (bench.HERE,)).CHUNK


@pytest.mark.parametrize("text,scope", [
    ("jit(train_step)/layer_0/block/delta/delta_scan/while/body/dot_general",
     "delta_scan"),
    ("transpose(jvp(delta_proj))/dot_general", "delta_proj"),
    ("jit(train_step)/layer_1/block/delta/delta_conv/add", "delta_conv"),
    ("jit(train_step)/layer_3/block/attn/attn_proj/dot_general", None),
    ("jit(train_step)/layer_1/block/delta/dot_general", None),
])
def test_scope_of_an_ops_text(text, scope):
    assert scope_times.scope_of(text, DELTA) == scope
