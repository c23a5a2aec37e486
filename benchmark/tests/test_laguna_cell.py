"""The gated-attention sparse model's cell on the CPU: its driver end to end
at a tiny fixture configuration (the comparison with the reference
included), the held and the published parameter counts reckoned from the
JSON and counted in the built tree, the counting form and ``attention_cost``
/ ``experts_cost`` pinned at the cell's sizes against hand numbers (72 query
heads on the sliding layers, 48 on the full ones), the lower-precision
control and the four planted faults through ``compare`` at hidden 128, and
the new reader on a rehearsed table of scope times. Per-layer entries are
looked up by name, never by position."""

import json
import os
import time

import pytest

from benchmark import run as bench
from benchmark.lib import flops, scope_names, scope_times, trace

ROOT = bench.ROOT
FIXTURES = os.path.join(bench.HERE, "tests", "fixtures")
TINY = "tiny_laguna.tiny_packed"
CELL = "laguna_s_2_1.packed8k"
COMPARED = {"loss_abs", "logits_abs", "grad_rel_l2_head",
            "grad_rel_l2_first_wg", "grad_rel_l2_first_wq",
            "grad_rel_l2_first_router", "grad_rel_l2_first_shared_in",
            "grad_rel_l2_first_w_down", "grad_rel_l2_full_wq",
            "grad_rel_l2_worst", "attn_gate_mean_abs", "pairs_local_rel",
            "load_max_over_mean_abs", "dropped_pairs"}
NEW = ["attn_gate_ms.train"]
GATE = ("attn_gate",)
APPENDED = {"device_step_ms.train", "attn_roofline.train",
            "attn_window_ms.train", "attn_full_ms.train", "mlp_ms.train",
            "moe_experts_roofline.train", "moe_route_ms.train",
            "moe_load_max_over_mean.train", "moe_shared_ms.train",
            "input_wait_ms.train", "prefetch_place_ms.train",
            "ring_wait_ms.train", "ring_wait_ms_p95.train",
            "host_step_ms.train", "step_call_ms.train", "model_sync_ms.train",
            "epoch_turnover_ms.train"}


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
    assert names == parts["cell"]["per_layer"] and len(names) == 23
    declared = _declared()
    cell = next(w for w in declared["workloads"] if w["name"] == CELL)
    assert cell["why"] == parts["cell"]["why"] and len(cell["why"]) <= 200
    assert cell["chips"] == 1 and cell["traffic"] == "packed8k"
    assert cell["config"] == "laguna_s_2_1" == parts["cell"]["config"]
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
    assert by_name["attn_gate_ms.train"]["workloads"] == [CELL]
    # the accepted entries it reports name it last, after what they had
    for name in APPENDED:
        assert by_name[name]["workloads"][-1] == CELL, name
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
    assert 0.3 < compared["counters"]["attn_gate_mean"] < 0.7
    assert compared["counters"]["attn_gate_mean"] == pytest.approx(
        compared["reference_counters"]["attn_gate_mean"], abs=1e-6)
    assert any(ln.get("agrees") is True for ln in lines)


def _hidden_128(parts):
    """What the limits rest on, at a size the CPU can do: hidden 128, T 256,
    bfloat16 operands stated, 12 / 18 query heads over 3 K/V heads of 16,
    window 64, 4 of 16 experts held, 4 a token, N(0, 0.02) weights as the
    cell's (at 0.08 the x 2.5 routed sum turns rounding into swapped experts
    and the system reads as the control does)."""
    cfg = json.loads(json.dumps(parts["cfg"]))
    cfg.update(hidden_size=128, intermediate_size=256, num_attention_heads=12,
               num_key_value_heads=3, head_dim=16,
               num_attention_heads_per_layer=[12, 18, 18, 18, 12],
               sliding_window=64, moe_intermediate_size=64,
               shared_expert_intermediate_size=64, num_experts=4,
               router_width=16, experts_held=list(range(4)),
               num_experts_per_tok=4, vocab_size=512, initializer_range=0.02)
    cfg["dtypes"] = {"compute": "bfloat16", "activation": "float32"}
    cfg["deployment"]["record_tokens"] = 256
    # the system reads (seed 2**31 + 5) loss 1.5e-4, head 0.0040, router
    # 0.010, w_down 0.0098, gate mean 8.8e-6, pairs 8.5e-4; the control
    # (bfloat16 throughout) 6.4e-4, 0.0094, 0.029, 0.053, 3.1e-4, 2.5e-3:
    # those six tell the two apart here, the rest sit above both
    cfg["correct"]["reference"] = {
        "loss_abs": 3e-4, "logits_abs": 0.07, "grad_rel_l2_head": 0.006,
        "grad_rel_l2_first_wg": 0.02, "grad_rel_l2_first_wq": 0.021,
        "grad_rel_l2_first_router": 0.02, "grad_rel_l2_first_shared_in": 0.017,
        "grad_rel_l2_first_w_down": 0.025, "grad_rel_l2_full_wq": 0.025,
        "grad_rel_l2_worst": 0.12, "attn_gate_mean_abs": 5e-5,
        "pairs_local_rel": 0.0018, "load_max_over_mean_abs": 0.01,
        "dropped_pairs": 0}
    return cfg, {**parts["mix"], "record_tokens": 256}


STAND_INS = [
    ("system", None), ("lower", {"dtype": "bfloat16"}),
    ("ungated", {"attention_gate": False}),
    ("whole_head_rotary", {"whole_head_rotary": True}),
    ("unscaled_router", {"routed_scaling": 1.0}),
    ("ungated_shared_expert", {"shared_expert_gate": False})]


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
    assert list(mod.FAULTS) == [s for _, s in STAND_INS[2:]]


def test_a_tighter_limit_breaks_correct(capsys, tmp_path):
    with open(os.path.join(FIXTURES, "configs", "tiny_laguna.json")) as f:
        cfg = json.load(f)
    cfg["correct"]["reference"]["logits_abs"] = 0.0
    os.makedirs(tmp_path / "configs")
    (tmp_path / "configs" / "tiny_laguna.json").write_text(json.dumps(cfg))
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
            "peak_hbm_gib", "moe_load_max_over_mean.train"} <= set(
        result["metrics"])
    assert not {"attn_gate_ms.train", "attn_window_ms.train",
                "attn_full_ms.train", "mlp_ms.train", "moe_shared_ms.train",
                "attn_roofline.train"} & set(result["metrics"])
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
        table = scopes.ScopeTimes(6, seconds, {})
        only = lambda *names: scopes.ScopeTimes(  # noqa: E731
            6, {k: v for k, v in seconds.items() if k in names}, {})
        self._named_scope_times = {scope_times.HYBRID_SCOPES: table,
                                   scope_names.LATENT_SCOPES: table,
                                   GATE: only(*GATE)}
        self._scope_times = table


def test_every_scope_reader_prints_on_a_rehearsed_trace():
    """The new reader and the accepted scope readers over a table of device
    seconds by scope, as a chip's trace would give: each prints, and the
    rooflines are the hand numbers over their scopes' times."""
    parts = bench.load_cell(CELL)
    cfg, mod = parts["cfg"], parts["config_module"]
    seconds = {"attn_full": 0.030, "attn_window": 0.025, "attn_proj": 0.120,
               "attn_gate": 0.012, "mlp": 0.080, "moe_route": 0.040,
               "moe_experts": 0.015, "moe_shared": 0.030, "lm_head": 0.030}
    steps = [{"moe_pairs_local": 10240.0, "moe_load_max_over_mean": 3.0,
              "attn_gate_mean": 0.5}] * 6
    run = _Run(seconds, steps)
    run.forward = lambda: None
    kinds = [kind for kind, _, _ in mod.layers(cfg)]
    run.forward.attention_cost = lambda kind: mod.attention_cost(cfg, 1, kind)
    run.forward.experts_cost = lambda pairs: mod.experts_cost(cfg, pairs, 4)
    run.forward.layer_kinds = kinds
    got = {r.NAME: r.read(run) for r in parts["readers"]
           if r.NAME.startswith(("moe_", "attn_", "mlp_"))}
    assert got["attn_gate_ms.train"] == pytest.approx(12.0)
    assert got["attn_full_ms.train"] == pytest.approx(30.0)
    assert got["attn_window_ms.train"] == pytest.approx(25.0)
    assert got["mlp_ms.train"] == pytest.approx(80.0)
    assert got["moe_shared_ms.train"] == pytest.approx(30.0)
    assert got["moe_route_ms.train"] == pytest.approx(40.0)
    assert got["moe_load_max_over_mean.train"] == pytest.approx(3.0)
    # two full layers at 48 heads (0.8247 TFLOP each) and three sliding ones
    # at 72 (0.1498 each): 3 x 2.0989 TFLOP / 197 TFLOP/s = 32.0 ms of 55
    assert got["attn_roofline.train"] == pytest.approx(
        100 * 3 * (2.098858033152e12 / 197e12) / 0.055, rel=2e-3)
    assert any(ln.get("attn_roofline_bound") == "compute" for ln in run.logged)
    # 10,240 pairs over four layers, three products: 3 x 0.1933 TFLOP / 197
    # = 2.94 ms compute; 3 x (604 MB of weights + 294 MB of rows) / 819
    # GB/s = 3.29 ms: memory
    assert got["moe_experts_roofline.train"] == pytest.approx(
        100 * 3 * (897_581_056 / 819e9) / 0.015, rel=2e-3)
    assert any(ln.get("moe_experts_roofline_bound") == "memory"
               for ln in run.logged)
    assert all(0 < got[k] < 100 for k in got if k.endswith("roofline.train"))


def test_the_new_reader_leaves_its_metric_out_where_the_program_has_no_such_scope():
    """The parent's program has no ``attn_gate`` scope."""
    parts = bench.load_cell(CELL)
    run = _Run({"attn_full": 0.03, "attn_proj": 0.1}, [])
    run.forward = lambda: None
    run.forward.layer_kinds = []
    for r in parts["readers"]:
        if r.NAME in NEW:
            assert r.read(run) is None, r.NAME


def test_counting_form_and_costs_against_hand_numbers():
    parts = bench.load_cell(CELL)
    cfg, mod = parts["cfg"], parts["config_module"]
    assert cfg["deployment"]["batch_per_chip"] == 1
    assert mod.layers(cfg) == [
        ("full_attention", 48, "dense")] + [
        ("sliding_attention", 72, "sparse")] * 3 + [
        ("full_attention", 48, "sparse")]
    assert mod.expected_pairs(cfg, 1) == 8192 * 10 * 8 // 256 == 2560
    prods = mod.products(cfg, 1)
    total = sum(2.0 * m * k * n for _, m, k, n in prods)
    by = lambda *keys: sum(  # noqa: E731
        2.0 * m * k * n for name, m, k, n in prods
        if name.split(".")[-1] in keys)
    tokens = 8192
    # a token: the projections (q, k, v, o and the gate) 2 x 3072 x (2 x 6144
    # + 2048 + 48) at 48 heads, (2 x 9216 + 2048 + 72) at 72
    assert by("wq", "wk", "wv", "wo", "wg") / tokens == 2 * 3072 * (
        2 * (2 * 6144 + 2048 + 48) + 3 * (2 * 9216 + 2048 + 72))
    assert by("wq", "wk", "wv", "wo", "wg") == pytest.approx(4.551e12, rel=1e-3)
    assert by("wg") / tokens == 2 * 3072 * (2 * 48 + 3 * 72)
    # the cores: 4 x 128 a visible pair and head
    assert by("qk", "pv") == 4 * 128 * (
        2 * 48 * 8192 * 8193 // 2 + 3 * 72 * (512 * 513 // 2 + 7680 * 512))
    assert by("w_in", "w_out") / tokens == 2 * 3 * 3072 * 12288
    assert by("shared_in", "shared_out", "shared_gate") / tokens == \
        4 * 2 * 3072 * (3 * 1024 + 1)
    assert by("router") / tokens == 4 * 2 * 3072 * 256
    assert by("w_gate", "w_up", "w_down") == 4 * 2560 * 2 * 3 * 3072 * 1024
    assert by("head") == 2.0 * tokens * 3072 * 12544
    # 1.2207 G a token forward, 10.0 TFLOP a step of 8192 tokens, two thirds
    # of it in the attention layers
    assert total == 10_000_322_789_376.0
    assert (by("wq", "wk", "wv", "wo", "wg", "qk", "pv") / total
            == pytest.approx(0.665, abs=1e-3))
    # and the walk over the counting form's jaxpr counts the same
    import jax
    import jax.numpy as jnp

    shapes = [(m, k, n) for _, m, k, n in prods]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for m, k, n in shapes for s in ((m, k), (k, n))]
    costs = flops.matmul_costs(
        lambda *a: [jnp.dot(x, w) for x, w in zip(a[::2], a[1::2])], *args)
    assert sum(c.flops for c in costs) == pytest.approx(total, rel=1e-9)
    full, nbytes = mod.attention_cost(cfg, 1, "full_attention")
    assert full == 4.0 * 128 * 48 * (8192 * 8193 // 2) == pytest.approx(
        0.8247e12, rel=1e-3)
    assert nbytes == 8192 * 128 * 2 * (48 + 8) * 2
    window, wbytes = mod.attention_cost(cfg, 1, "sliding_attention")
    assert window == 4.0 * 128 * 72 * (512 * 513 // 2 + 7680 * 512)
    assert wbytes == 8192 * 128 * 2 * (72 + 8) * 2
    eflops, ebytes = mod.experts_cost(cfg, 10240, 4)
    assert eflops == 10240 * 6.0 * 3072 * 1024
    assert ebytes == 4 * 8 * 3 * 3072 * 1024 * 2 + 10240 * (
        3072 * 2 + 2 * 1024 * 4 + 1024 * 2 + 3072 * 4) == 897_581_056


def test_configuration_keeps_every_published_width():
    # the published configuration, as its model card gives it
    with open(os.path.join(FIXTURES, "catalog", "laguna_s_2_1.json")) as f:
        row = json.load(f)
    assert row["name"] == "Laguna-S-2.1"
    cfg = bench.load_json("configs", "laguna_s_2_1", (bench.HERE,))
    changed = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert changed == set(cfg["reduced"]) == set(cfg["reduced_how"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    declared = next(c for c in _declared()["configs"]
                    if c["name"] == "laguna_s_2_1")
    assert declared["reduced"] == cfg["reduced"]
    assert declared["source"] == cfg["source"] == row["source_url"]
    assert declared["file"] == "benchmark/configs/laguna_s_2_1.json"
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] == len(cfg["experts_held"]) == 8
    assert cfg["router_width"] == cfg["published"]["num_experts"] == 256
    assert cfg["deployment"]["chips_sharing_a_layer"] == 32
    assert cfg["num_hidden_layers"] == 5 < cfg["published"]["num_hidden_layers"]
    # reckoned from the JSON's own keys
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    hkv = cfg["num_key_value_heads"]

    def attention(hq):
        return d * hd * (2 * hq + 2 * hkv) + d * hq + 2 * hd

    dense = 3 * d * cfg["intermediate_size"]
    outside = d * cfg["router_width"] + 3 * d * cfg[
        "shared_expert_intermediate_size"] + d
    expert = 3 * d * cfg["moe_intermediate_size"]
    held, published = cfg["held"], cfg["published"]
    assert attention(48) + dense + 2 * d == held["parameters_dense_layer"] \
        == published["parameters_dense_layer"] == 157_440_256
    assert attention(72) + outside + 2 * d + 8 * expert \
        == held["parameters_sliding_layer"] == 148_866_304
    assert attention(48) + outside + 2 * d + 8 * expert \
        == held["parameters_full_layer"] == 129_918_208
    assert attention(72) + outside + 2 * d + 256 * expert \
        == published["parameters_sliding_layer"]
    assert attention(48) + outside + 2 * d + 256 * expert \
        == published["parameters_full_layer"]
    assert held["parameters"] == held["parameters_dense_layer"] \
        + 3 * held["parameters_sliding_layer"] \
        + held["parameters_full_layer"] + 2 * v * d + d == 811_030_784
    assert held["bytes_at_16_per_parameter"] == 16 * held["parameters"]
    assert published["parameters"] == published["parameters_dense_layer"] \
        + 11 * published["parameters_full_layer"] \
        + 36 * published["parameters_sliding_layer"] \
        + 2 * published["vocab_size"] * d + d == 117_562_109_952
    for key in ("router_scores", "qk_norm", "shared_expert_gate",
                "attention_gate_input", "auxiliary_loss", "initialisation",
                "optimizer", "records", "records_per_step"):
        assert key in cfg["assumed"], key
    assert set(cfg["correct"]["reference"]) - {"why"} == COMPARED
    # and the program's own parameter tree holds exactly that many numbers,
    # at the published widths
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import decoder_lm

    mod = bench.load_module("configs", "laguna_s_2_1", (bench.HERE,))
    model = decoder_lm.from_config(mod.model_config(cfg))
    shapes = jax.eval_shape(
        lambda: (model.build(jax.random.PRNGKey(0), jax.ShapeDtypeStruct(
            (1, 256), jnp.int32)), model.get_parameters())[1])
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) == \
        held["parameters"]
    layer = lambda i, part: shapes[f"layer_{i}"]["block"][part]  # noqa: E731
    assert layer(1, "attn")["wq"].shape == (3072, 72 * 128)
    assert layer(4, "attn")["wq"].shape == (3072, 48 * 128)
    assert layer(1, "attn")["wg"].shape == (3072, 72)
    assert layer(0, "mlp")["w_in"].shape == (3072, 2 * 12288)
    assert layer(2, "experts")["w_up"].shape == (8, 3072, 1024)
    assert layer(2, "experts")["router"].shape == (3072, 256)
    assert layer(2, "experts")["shared_gate"].shape == (3072, 1)
    blocks = [m.modules[0] for m in model.modules if hasattr(m, "modules")
              and m.modules and type(m.modules[0]).__name__ == "DecoderBlock"]
    attn = [b.modules[1] for b in blocks]
    assert [(a.num_heads, a.window, a.rotary_dim) for a in attn] == [
        (48, None, 64)] + [(72, 512, None)] * 3 + [(48, None, 64)]
    experts = blocks[1].modules[3]
    assert (experts.top_k, experts.routed_scaling, experts.shared_size) == (
        10, 2.5, 1024)


@pytest.mark.parametrize("text,names,scope", [
    ("jit(train_step)/layer_1/block/attn/attn_gate/logistic", GATE,
     "attn_gate"),
    ("transpose(jvp(attn_gate))/mul", GATE, "attn_gate"),
    ("jit(train_step)/layer_1/block/attn/attn_proj/dot_general", GATE, None),
    ("jit(train_step)/layer_1/block/experts/moe_shared/logistic",
     scope_names.LATENT_SCOPES, "moe_shared"),
    ("jit(train_step)/layer_0/block/mlp/mlp/dot_general",
     scope_times.HYBRID_SCOPES, "mlp"),
])
def test_scope_of_an_ops_text(text, names, scope):
    assert scope_times.scope_of(text, names) == scope
