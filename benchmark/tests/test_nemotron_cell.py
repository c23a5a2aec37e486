"""The one-mixer-a-layer hybrid's cell on the CPU: its driver end to end at a
tiny fixture configuration (the comparison with the reference included), the
held and the published parameter counts reckoned from the JSON, the counting
form, ``ssd_cost`` / ``experts_cost`` / ``attention_cost`` pinned at the
cell's sizes against hand numbers, the lower-precision control and the four
planted faults through ``compare`` at hidden 128, and the new reader on a
rehearsed table of scope times."""

import json
import os
import time

import pytest

from benchmark import run as bench
from benchmark.lib import flops, scope_names, scope_times, trace

ROOT = bench.ROOT
FIXTURES = os.path.join(bench.HERE, "tests", "fixtures")
TINY = "tiny_nemotron.tiny_packed"
CELL = "nemotron_3_nano_30b_a3b.packed8k"
COMPARED = {"loss_abs", "logits_abs", "grad_rel_l2_head",
            "grad_rel_l2_first_A_log", "grad_rel_l2_first_dt_bias",
            "grad_rel_l2_first_conv", "grad_rel_l2_first_in_proj_bc",
            "grad_rel_l2_first_router", "grad_rel_l2_first_shared_in",
            "grad_rel_l2_first_w_down", "grad_rel_l2_worst",
            "log_decay_min_rel", "state_rms_rel", "pairs_local_rel",
            "load_max_over_mean_abs", "dropped_pairs", "bias_abs_max_abs",
            "bias_mismatched"}
NEW = ["ssm_gate_norm_ms.train"]
GATE_NORM = ("ssm_gate_norm",)


def _rehearse(trace_on, capsys, **extra):
    rehearsal = {"platform": "cpu", **extra}
    result = bench.run_cell(TINY, 2**31 + 79, 1.0, trace_on,
                            t0=time.perf_counter(),
                            roots=(FIXTURES, bench.HERE), rehearsal=rehearsal)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return result, lines


def test_the_cells_files_load_by_name():
    parts = bench.load_cell(CELL)
    assert parts["cell"]["driver"] == "train_ref" and parts["cell"]["chips"] == 1
    assert parts["cell"]["traffic"] == "packed8k"
    assert parts["mix"]["generator"] == "token_records"
    names = [r.NAME for r in parts["readers"]]
    assert names == parts["cell"]["per_layer"] and len(names) == 25
    assert "epoch_turnover_ms.train" not in names   # a window holds no turn
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = json.load(f)
    cell = next(w for w in declared["workloads"] if w["name"] == CELL)
    assert cell["why"] == parts["cell"]["why"] and len(cell["why"]) <= 200
    assert cell["chips"] == 1 and cell["traffic"] == "packed8k"
    # every per-layer name of the cell has a reader and a manifest entry that
    # lists the cell, and no other entry lists it
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
    assert by_name["ssm_gate_norm_ms.train"]["workloads"] == [CELL]
    assert declared["per_layer"][-1]["name"] == "ssm_gate_norm_ms.train"


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
    # the comparison runs on biases that are not zero, and counts every pair
    assert compared["counters"]["moe_bias_abs_max"] > 0.2
    assert compared["counters"]["ssm_log_decay_min"] < 0
    assert any(ln.get("agrees") is True for ln in lines)
    means = next(ln for ln in lines if "window_mean_of_counters" in ln)
    assert {"moe_pairs_local", "moe_bias_abs_max"} <= set(
        means["window_mean_of_counters"])


def _hidden_128(parts):
    """What the limits rest on, at a size the CPU can do: hidden 128, T 256,
    bfloat16 operands stated, 16 heads of 16 in 4 B/C groups."""
    cfg = json.loads(json.dumps(parts["cfg"]))
    cfg.update(hidden_size=128, num_attention_heads=8, num_key_value_heads=2,
               head_dim=16, mamba_num_heads=16, mamba_head_dim=16,
               ssm_state_size=32, n_groups=4, chunk_size=64,
               moe_intermediate_size=96,
               moe_shared_expert_intermediate_size=192, n_routed_experts=4,
               router_width=16, experts_held=list(range(4)),
               num_experts_per_tok=4, vocab_size=512, initializer_range=0.08)
    cfg["dtypes"] = {"compute": "bfloat16", "activation": "float32"}
    cfg["deployment"]["record_tokens"] = 256
    cfg["correct"]["reference"] = {
        "loss_abs": 2e-3, "logits_abs": 0.05, "grad_rel_l2_head": 0.012,
        "grad_rel_l2_first_A_log": 0.05, "grad_rel_l2_first_dt_bias": 0.05,
        "grad_rel_l2_first_conv": 0.03, "grad_rel_l2_first_in_proj_bc": 0.03,
        "grad_rel_l2_first_router": 0.05, "grad_rel_l2_first_shared_in": 0.02,
        "grad_rel_l2_first_w_down": 0.03, "grad_rel_l2_worst": 0.12,
        "log_decay_min_rel": 0.003, "state_rms_rel": 0.01,
        "pairs_local_rel": 0.01, "load_max_over_mean_abs": 0.05,
        "dropped_pairs": 0, "bias_abs_max_abs": 0.0021, "bias_mismatched": 0,
        "bias_count_slack": 4}
    return cfg, {**parts["mix"], "record_tokens": 256}


STAND_INS = [
    ("system", None), ("lower", {"dtype": "bfloat16"}),
    ("group_zero_for_every_head", {"scan_group_zero": True}),
    ("norm_over_all_of_d_inner", {"norm_over_all": True}),
    ("gated_expert", {"gated_expert": True}),
    ("bias_in_weights", {"bias_in_weights": True})]


@pytest.fixture(scope="module")
def readings():
    """``compare`` at hidden 128: the system, the lower-precision control and
    the four planted faults in its place."""
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


def test_the_planted_faults_are_the_modules_own_list():
    mod = bench.load_cell(CELL)["config_module"]
    assert set(mod.FAULTS) == {next(iter(s)) for _, s in STAND_INS[2:]}


def test_a_tighter_limit_breaks_correct(capsys, tmp_path):
    with open(os.path.join(FIXTURES, "configs", "tiny_nemotron.json")) as f:
        cfg = json.load(f)
    cfg["correct"]["reference"]["logits_abs"] = 0.0
    os.makedirs(tmp_path / "configs")
    (tmp_path / "configs" / "tiny_nemotron.json").write_text(json.dumps(cfg))
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
    # counters' readers are there
    assert {"dispatch_ms.train", "compile_first_dispatch_s",
            "model_flops_util_pct.train", "device_idle_pct.train",
            "peak_hbm_gib", "moe_load_max_over_mean.train",
            "moe_bias_abs_max.train"} <= set(result["metrics"])
    assert not {"ssm_gate_norm_ms.train", "ssm_proj_ms.train",
                "ssm_scan_ms.train", "moe_shared_ms.train",
                "attn_full_ms.train"} & set(result["metrics"])
    assert result["metrics"]["model_flops_util_pct.train"]["value"] > 0
    assert 0 < result["metrics"]["moe_bias_abs_max.train"]["value"] < 0.05


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
        table = scopes.ScopeTimes(6, seconds, {})
        only = lambda *names: scopes.ScopeTimes(  # noqa: E731
            6, {k: v for k, v in seconds.items() if k in names}, {})
        self._named_scope_times = {scope_times.HYBRID_SCOPES: table,
                                   scope_names.LATENT_SCOPES: table,
                                   GATE_NORM: only(*GATE_NORM)}
        self._scope_times = table


def test_every_scope_reader_prints_on_a_rehearsed_trace():
    """The new reader and the accepted scope readers over a table of device
    seconds by scope, as a chip's trace would give: each prints, and the
    rooflines are the hand numbers over their scopes' times."""
    parts = bench.load_cell(CELL)
    cfg, mod = parts["cfg"], parts["config_module"]
    seconds = {"attn_full": 0.040, "attn_proj": 0.015, "ssm_proj": 0.150,
               "ssm_gate_norm": 0.030, "ssm_conv": 0.040, "ssm_scan": 0.120,
               "moe_route": 0.100, "moe_experts": 0.020, "moe_shared": 0.090,
               "lm_head": 0.035}
    steps = [{"moe_pairs_local": 24576.0, "moe_bias_abs_max": 0.02,
              "moe_load_max_over_mean": 3.0}] * 6
    run = _Run(seconds, steps)
    run.forward = lambda: None
    kinds = mod.layer_kinds(cfg)
    run.forward.attention_cost = lambda kind: mod.attention_cost(cfg, 2, kind)
    run.forward.ssd_cost = lambda: mod.ssd_cost(cfg, 2)
    run.forward.experts_cost = lambda pairs: mod.experts_cost(cfg, pairs, 4)
    run.forward.layer_kinds = kinds
    got = {r.NAME: r.read(run) for r in parts["readers"]
           if r.NAME.startswith(("ssm_", "moe_", "attn_"))}
    assert got["ssm_gate_norm_ms.train"] == pytest.approx(30.0)
    assert got["ssm_proj_ms.train"] == pytest.approx(150.0)   # holds the norm
    assert got["ssm_conv_ms.train"] == pytest.approx(40.0)
    assert got["ssm_scan_ms.train"] == pytest.approx(120.0)
    assert got["moe_shared_ms.train"] == pytest.approx(90.0)
    assert got["moe_route_ms.train"] == pytest.approx(100.0)
    assert got["attn_full_ms.train"] == pytest.approx(40.0)
    assert got["moe_bias_abs_max.train"] == pytest.approx(0.02)
    assert got["moe_load_max_over_mean.train"] == pytest.approx(3.0)
    # one attention layer: 3 x 1.0996 TFLOP / 197 TFLOP/s = 16.7 ms of 40
    assert got["attn_roofline.train"] == pytest.approx(
        100 * 3 * (1.0996e12 / 197e12) / 0.040, rel=2e-3)
    # four scans: 3 x 4 x 474 MB / 819 GB/s = 6.9 ms (memory: 45.2 GFLOP a
    # layer are 0.23 ms of compute) of 120
    assert got["ssm_scan_roofline.train"] == pytest.approx(
        100 * 3 * 4 * (473956352 / 819e9) / 0.120, rel=2e-3)
    assert any(ln.get("ssm_scan_roofline_bound") == "memory"
               for ln in run.logged)
    # 24,576 pairs over four layers, TWO products: 3 x 0.490 TFLOP / 197 =
    # 7.5 ms compute; 3 x (639 MB of weights + 670 MB of rows) / 819 GB/s =
    # 4.8 ms: compute
    assert got["moe_experts_roofline.train"] == pytest.approx(
        100 * 3 * (24576 * 4 * 2688 * 1856 / 197e12) / 0.020, rel=2e-3)
    assert any(ln.get("moe_experts_roofline_bound") == "compute"
               for ln in run.logged)
    assert all(0 < got[k] < 100 for k in got if k.endswith("roofline.train"))


def test_the_new_reader_leaves_its_metric_out_where_the_program_has_no_such_scope():
    """The parent's program has no ``ssm_gate_norm`` scope."""
    parts = bench.load_cell(CELL)
    run = _Run({"ssm_proj": 0.15, "ssm_scan": 0.1}, [])
    run.forward = lambda: None
    run.forward.layer_kinds = []
    for r in parts["readers"]:
        if r.NAME in NEW:
            assert r.read(run) is None, r.NAME


def test_counting_form_and_costs_against_hand_numbers():
    parts = bench.load_cell(CELL)
    cfg, mod = parts["cfg"], parts["config_module"]
    assert cfg["deployment"]["batch_per_chip"] == 2
    assert mod.layer_kinds(cfg) == ["experts", "mamba"] * 4 + ["attention"]
    assert mod.expected_pairs(cfg, 2) == 16384 * 6 * 8 // 128 == 6144
    prods = mod.products(cfg, 2)
    total = sum(2.0 * m * k * n for _, m, k, n in prods)
    by = lambda *keys: sum(  # noqa: E731
        2.0 * m * k * n for name, m, k, n in prods
        if name.split(".")[-1] in keys)
    tokens = 2 * 8192
    # a token and layer at 8192: a Mamba layer's projections 77.4 M and its
    # scan 2.76 M (8 groups' C.B^T 0.13, (C.B^T * L).X 0.53, the states 2.10);
    # an expert layer's shared expert 39.9 M, router 0.69 M, local routed
    # pairs 7.48 M; the attention layer 113.9 M; the head 88.1 M
    assert by("in_proj", "out_proj") / (4 * tokens) == pytest.approx(
        2 * 2688 * (10304 + 4096), rel=1e-12)
    assert by("in_proj", "out_proj") / (4 * tokens) == pytest.approx(
        77.4e6, rel=1e-3)
    assert by("scan_cb") / (4 * tokens) == 2 * 8 * 128 * 64.5
    assert by("scan_lx") / (4 * tokens) == 2 * 4096 * 64.5
    assert by("scan_states", "scan_cs") / (4 * tokens) == 4 * 4096 * 128
    assert by("shared_in", "shared_out") / (4 * tokens) == 4 * 2688 * 3712
    assert by("router") / (4 * tokens) == 2 * 2688 * 128
    assert by("w_up", "w_down") / (4 * tokens) == pytest.approx(
        7.483e6, rel=1e-3)
    assert by("wq", "wk", "wv", "wo", "qk", "pv") / tokens == pytest.approx(
        2 * 2688 * (4096 + 512 + 4096) + 4 * 128 * 32 * 8193 / 2, rel=1e-12)
    assert by("wq", "wk", "wv", "wo", "qk", "pv") / tokens == pytest.approx(
        113.9e6, rel=1e-3)
    assert by("head") == 2.0 * tokens * 2688 * 16384
    # 715.0 M a token forward, 11.71 TFLOP a step of 16,384 tokens (35.1 with
    # the backward pass, before recomputation)
    assert total / tokens == pytest.approx(715.01e6, rel=1e-4)
    assert total == 11_714_724_626_432.0
    # and the walk over the counting form's jaxpr counts the same
    import jax
    import jax.numpy as jnp

    shapes = [(m, k, n) for _, m, k, n in prods]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for m, k, n in shapes for s in ((m, k), (k, n))]
    costs = flops.matmul_costs(
        lambda *a: [jnp.dot(x, w) for x, w in zip(a[::2], a[1::2])], *args)
    assert sum(c.flops for c in costs) == pytest.approx(total, rel=1e-9)
    full, nbytes = mod.attention_cost(cfg, 2, "attention")
    assert full == 4.0 * 128 * 2 * 32 * (8192 * 8193 // 2) == pytest.approx(
        1.0996e12, rel=1e-3)
    assert nbytes == 2 * 8192 * 128 * 2 * (32 + 2) * 2
    assert mod.attention_cost(cfg, 2, "mamba") == (0.0, 0.0)
    assert mod.attention_cost(cfg, 2, "experts") == (0.0, 0.0)
    sflops, sbytes = mod.ssd_cost(cfg, 2)
    in_chunk = 64 * 128 * 129 // 2
    assert sflops == 2 * (2.0 * 128 * 8 * in_chunk + 2.0 * 4096 * in_chunk
                          + 4.0 * 8192 * 4096 * 128) == 45_181_042_688.0
    # x bf16 + y f32 + B and C of 8 groups bf16 + dt f32, a token
    assert sbytes == 2 * 8192 * (4096 * 2 + 4096 * 4 + 2 * 8 * 128 * 2
                                 + 64 * 4) == 473_956_352
    eflops, ebytes = mod.experts_cost(cfg, 24576, 4)
    assert eflops == 24576 * 4.0 * 2688 * 1856
    assert ebytes == 4 * 8 * 2 * 2688 * 1856 * 2 + 24576 * (
        2688 * 2 + 1856 * 4 + 1856 * 2 + 2688 * 4)


def test_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(ln) for ln in f
                   if '"name": "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in ln)
    cfg = bench.load_json("configs", "nemotron_3_nano_30b_a3b", (bench.HERE,))
    changed = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = next(c for c in json.load(f)["configs"]
                        if c["name"] == "nemotron_3_nano_30b_a3b")
    assert declared["reduced"] == cfg["reduced"]
    assert declared["source"] == cfg["source"] == row["source_url"]
    assert declared["file"] == "benchmark/configs/nemotron_3_nano_30b_a3b.json"
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] == len(cfg["experts_held"]) == 8
    assert cfg["router_width"] == cfg["published"]["n_routed_experts"] == 128
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
    # the published pattern whole, and the stage cut from it
    pattern = cfg["hybrid_override_pattern"]
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (
        23, 23, 6) and len(pattern) == cfg["published"]["num_hidden_layers"]
    first = cfg["first_layer"]
    assert pattern[first:first + cfg["num_hidden_layers"]] == "EMEMEMEM*"
    # reckoned from the JSON's own keys
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    bc = 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    mamba = d * (2 * h * p + bc + h) + (h * p + bc) * (cfg["conv_kernel"] + 1) \
        + 3 * h + h * p + h * p * d + d
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    attention = d * hd * (2 * hq + 2 * hkv) + d
    expert = 2 * d * cfg["moe_intermediate_size"]
    outside = 2 * d * cfg["moe_shared_expert_intermediate_size"] \
        + d * cfg["router_width"] + d
    held, published = cfg["held"], cfg["published"]
    assert mamba == held["parameters_per_mamba_layer"] == 38_744_896 \
        == published["parameters_per_mamba_layer"]
    assert attention == held["parameters_per_attention_layer"] == 23_399_040
    assert (expert, outside) == (9_977_856, 20_302_464)
    assert held["parameters_per_expert_layer"] == outside + 8 * expert
    assert published["parameters_per_expert_layer"] == outside + 128 * expert
    assert held["parameters"] == 4 * mamba + attention \
        + 4 * held["parameters_per_expert_layer"] + 2 * v * d + d == 666_962_944
    assert held["bytes_at_16_per_parameter"] == 16 * held["parameters"]
    assert published["parameters"] == 23 * mamba + 6 * attention \
        + 23 * published["parameters_per_expert_layer"] \
        + 2 * published["vocab_size"] * d + d == 31_577_937_344
    for key in ("router_bias_update_rate", "auxiliary_loss",
                "positional_encoding", "initialisation", "optimizer",
                "records_per_step", "records"):
        assert key in cfg["assumed"], key
    for key in COMPARED | {"bias_count_slack"}:
        assert key in cfg["correct"]["reference"], key
    # and the program's own parameter tree holds exactly that many numbers
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import decoder_lm

    model = decoder_lm.from_config(cfg)
    shapes = jax.eval_shape(
        lambda: (model.build(jax.random.PRNGKey(0), jax.ShapeDtypeStruct(
            (1, 256), jnp.int32)), model.get_parameters())[1])
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) == \
        held["parameters"]
    assert shapes["layer_0"]["block"]["experts"]["w_up"].shape == (8, 2688, 1856)


@pytest.mark.parametrize("text,names,scope", [
    ("jit(train_step)/layer_1/block/ssm/ssm_proj/ssm_gate_norm/mul",
     GATE_NORM, "ssm_gate_norm"),
    ("jit(train_step)/layer_1/block/ssm/ssm_proj/ssm_gate_norm/mul",
     scope_times.HYBRID_SCOPES, "ssm_proj"),     # the accepted reader's own
    ("transpose(jvp(ssm_gate_norm))/rsqrt", GATE_NORM, "ssm_gate_norm"),
    ("jit(train_step)/layer_1/block/ssm/ssm_proj/dot_general", GATE_NORM, None),
    ("jit(train_step)/layer_0/block/experts/moe_shared/dot_general",
     scope_names.LATENT_SCOPES, "moe_shared"),
    ("jit(train_step)/layer_0/block/experts/moe_experts/gmm",
     scope_names.LATENT_SCOPES, "moe_experts"),
    ("jit(train_step)/layer_8/block/attn/attn_full/flash_fwd",
     scope_names.LATENT_SCOPES, "attn_full"),
])
def test_scope_of_an_ops_text(text, names, scope):
    assert scope_times.scope_of(text, names) == scope
