"""The latent-attention sparse model's cell on the CPU: its driver end to end
at a tiny fixture configuration (the comparison with the reference included),
the counting form, ``attention_cost`` and ``experts_cost`` pinned at the
cell's sizes against hand numbers, the lower-precision control and the planted
faults through ``compare`` at hidden 128, and the new readers on a rehearsed
table of scope times."""

import json
import os
import time

import pytest

from benchmark import run as bench
from benchmark.lib import flops, scope_names, scope_times, trace

ROOT = bench.ROOT
FIXTURES = os.path.join(bench.HERE, "tests", "fixtures")
TINY = "tiny_joyai.tiny_packed"
CELL = "joyai_llm_flash.packed8k"
COMPARED = {"loss_abs", "main_loss_abs", "mtp_loss_abs", "logits_abs",
            "mtp_logits_abs", "grad_rel_l2_head", "grad_rel_l2_first_router",
            "grad_rel_l2_first_wkv_b", "grad_rel_l2_first_w_down",
            "grad_rel_l2_worst", "pairs_local_rel",
            "load_max_over_mean_abs", "dropped_pairs", "bias_abs_max_abs",
            "bias_mismatched"}
NEW = ["mla_proj_ms.train", "moe_shared_ms.train", "mtp_ms.train",
       "moe_bias_abs_max.train"]


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
    assert names == parts["cell"]["per_layer"] and len(names) == 23
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = json.load(f)
    cell = next(w for w in declared["workloads"] if w["name"] == CELL)
    assert cell["why"] == parts["cell"]["why"] and len(cell["why"]) <= 200
    for m in declared["per_layer"]:
        reported = m["name"] in names
        listed = CELL in m.get("workloads", [CELL])
        assert reported == listed, m["name"]
    reader = {r.NAME: r for r in parts["readers"]}
    # looked up by name: a later PR appends its own entries after these
    mine = [m for m in declared["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == NEW
    for m in mine:
        r = reader[m["name"]]
        assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
        assert m["workloads"][0] == CELL


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
    assert compared["counters"]["mtp_loss"] > 4.0
    assert any(ln.get("agrees") is True for ln in lines)
    means = next(ln for ln in lines if "window_mean_of_counters" in ln)
    assert {"moe_pairs_local", "moe_bias_abs_max"} <= set(
        means["window_mean_of_counters"])


def _hidden_128(parts):
    """What the limits rest on, at a size the CPU can do: hidden 128, T 256,
    bfloat16 operands stated."""
    cfg = json.loads(json.dumps(parts["cfg"]))
    cfg.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=4,
               q_lora_rank=96, kv_lora_rank=64, qk_nope_head_dim=32,
               qk_rope_head_dim=16, qk_head_dim=48, v_head_dim=32,
               intermediate_size=256, moe_intermediate_size=64,
               n_routed_experts=4, router_width=16,
               experts_held=list(range(4)), num_experts_per_tok=4,
               vocab_size=512, initializer_range=0.08)
    cfg["dtypes"] = {"compute": "bfloat16", "activation": "float32"}
    cfg["deployment"]["record_tokens"] = 256
    cfg["correct"]["reference"] = {
        "loss_abs": 2e-3, "main_loss_abs": 2e-3, "mtp_loss_abs": 2e-3,
        "logits_abs": 0.05, "mtp_logits_abs": 0.05, "grad_rel_l2_head": 0.014,
        "grad_rel_l2_first_router": 0.02, "grad_rel_l2_first_wkv_b": 0.02,
        "grad_rel_l2_first_w_down": 0.03,
        "grad_rel_l2_worst": 0.12, "pairs_local_rel": 0.01,
        "load_max_over_mean_abs": 0.05, "dropped_pairs": 0,
        "bias_abs_max_abs": 1e-6, "bias_mismatched": 0, "bias_count_slack": 4}
    return cfg, {**parts["mix"], "record_tokens": 256}


STAND_INS = [
    ("system", None), ("lower", {"dtype": "bfloat16"}),
    ("half_split_rope", {"rope_interleave": False}),
    ("score_scale", {"softmax_scale": 32 ** -0.5}),
    ("bias_in_weights", {"bias_in_weights": True}),
    ("no_shared_expert", {"shared_expert": False}),
    ("no_mtp_loss", {"mtp_loss_weight": 0.0}),
    ("neighbours_experts", {"experts_held": list(range(1, 5))})]


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


def test_a_tighter_limit_breaks_correct(capsys, tmp_path):
    with open(os.path.join(FIXTURES, "configs", "tiny_joyai.json")) as f:
        cfg = json.load(f)
    cfg["correct"]["reference"]["mtp_logits_abs"] = 0.0
    os.makedirs(tmp_path / "configs")
    (tmp_path / "configs" / "tiny_joyai.json").write_text(json.dumps(cfg))
    result = bench.run_cell(TINY, 11, 1.0, False, t0=time.perf_counter(),
                            roots=(str(tmp_path), FIXTURES, bench.HERE),
                            rehearsal={"platform": "cpu"})
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert result["correct"] is False
    assert next(ln for ln in lines if "broken" in ln and "agrees" not in ln
                and "reference_comparison" in ln)["broken"] == ["mtp_logits_abs"]


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
    assert not {"mla_proj_ms.train", "moe_shared_ms.train", "mtp_ms.train",
                "mlp_ms.train", "attn_full_ms.train"} & set(result["metrics"])
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
        mtp = scopes.ScopeTimes(6, {k: v for k, v in seconds.items()
                                    if k == "mtp"}, {})
        self._named_scope_times = {scope_times.HYBRID_SCOPES: table,
                                   scope_names.LATENT_SCOPES: table,
                                   ("mtp",): mtp}
        self._scope_times = table


def test_every_scope_reader_prints_on_a_rehearsed_trace():
    """The four new readers and the accepted scope readers over a table of
    device seconds by scope, as a chip's trace would give: each prints, and
    the rooflines are the hand numbers over their scopes' times."""
    parts = bench.load_cell(CELL)
    cfg, mod = parts["cfg"], parts["config_module"]
    seconds = {"attn_full": 0.300, "mla_proj": 0.200, "moe_route": 0.160,
               "moe_experts": 0.010, "moe_shared": 0.025, "mlp": 0.040,
               "mtp": 0.170, "lm_head": 0.070}
    steps = [{"moe_pairs_local": 40960.0, "moe_bias_abs_max": 0.02,
              "moe_load_max_over_mean": 5.0}] * 6
    run = _Run(seconds, steps)
    run.forward = lambda: None
    run.forward.attention_cost = lambda kind: mod.attention_cost(cfg, 2, kind)
    run.forward.experts_cost = lambda pairs: mod.experts_cost(cfg, pairs, 5)
    run.forward.layer_kinds = ["full_attention"] * 6
    got = {r.NAME: r.read(run) for r in parts["readers"]
           if r.NAME.startswith(("mla_", "moe_", "mtp_", "mlp_", "attn_"))}
    assert got["mla_proj_ms.train"] == pytest.approx(200.0)
    assert got["moe_shared_ms.train"] == pytest.approx(25.0)
    assert got["mtp_ms.train"] == pytest.approx(170.0)
    assert got["mlp_ms.train"] == pytest.approx(40.0)
    assert got["attn_full_ms.train"] == pytest.approx(300.0)
    assert got["moe_route_ms.train"] == pytest.approx(160.0)
    assert got["moe_bias_abs_max.train"] == pytest.approx(0.02)
    assert got["moe_load_max_over_mean.train"] == pytest.approx(5.0)
    # six layers: 3 x 6 x 1.374 TFLOP / 197 TFLOP/s = 125.6 ms of 300
    assert got["attn_roofline.train"] == pytest.approx(41.86, rel=2e-3)
    # 40,960 pairs over five layers: 3 x 1.16 TFLOP / 197 = 17.7 ms compute,
    # 3 x (472 MB of weights + 778 MB of rows) / 819 GB/s = 4.6 ms: compute
    assert got["moe_experts_roofline.train"] == pytest.approx(
        100 * 3 * (40960 * 6 * 2048 * 768 / 197e12) / 0.010, rel=2e-3)
    assert any(ln.get("moe_experts_roofline_bound") == "compute"
               for ln in run.logged)


def test_readers_leave_their_metric_out_where_the_program_has_no_such_scope():
    """The parent's program has none of the new scopes and counters."""
    parts = bench.load_cell(CELL)
    run = _Run({"attn_full": 0.02}, [{"moe_pairs_local": 1.0}] * 6)
    run.forward = lambda: None
    run.forward.layer_kinds = []
    for r in parts["readers"]:
        if r.NAME in NEW:
            assert r.read(run) is None, r.NAME


def test_counting_form_and_costs_against_hand_numbers():
    parts = bench.load_cell(CELL)
    cfg, mod = parts["cfg"], parts["config_module"]
    assert cfg["deployment"]["batch_per_chip"] == 2
    assert mod._layers(cfg) == ["dense"] + ["sparse"] * 5
    prods = mod.products(cfg, 2)
    total = sum(2.0 * m * k * n for _, m, k, n in prods)
    by = lambda *keys: sum(  # noqa: E731
        2.0 * m * k * n for name, m, k, n in prods
        if name.split(".")[-1] in keys)
    # a token and layer at 8192: the attention core 83.9 M (causal mean), the
    # latent projections 52.7 M, the shared expert 9.4 M, local routed pairs 4.7 M
    per = 6 * 2 * 8192
    assert by("qk", "pv") / per == pytest.approx(
        32 * (2 * 192 + 2 * 128) * 8193 / 2, rel=1e-9)
    assert by("qk", "pv") / per == pytest.approx(83.9e6, rel=2e-3)
    assert by("wq_a", "wq_b", "wkv_a", "wkv_b", "wo") / per == pytest.approx(
        52.7e6, rel=2e-3)
    assert by("shared_in", "shared_out") / (5 * 2 * 8192) == pytest.approx(
        9.44e6, rel=2e-3)
    assert by("w_gate", "w_up", "w_down") / (5 * 2 * 8192) == pytest.approx(
        4.72e6, rel=2e-3)
    assert by("head") == 2 * 2.0 * 16384 * 2048 * 16160
    assert total == pytest.approx(
        by("qk", "pv") + by("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
        + by("mlp_in", "mlp_out") + by("router") + by("shared_in", "shared_out")
        + by("w_gate", "w_up", "w_down") + by("head") + by("eh_proj"), rel=1e-12)
    assert total == pytest.approx(18.56e12, rel=5e-4)
    # and the walk over the counting form's jaxpr counts the same
    import jax
    import jax.numpy as jnp

    shapes = [(m, k, n) for _, m, k, n in prods]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for m, k, n in shapes for s in ((m, k), (k, n))]
    costs = flops.matmul_costs(
        lambda *a: [jnp.dot(x, w) for x, w in zip(a[::2], a[1::2])], *args)
    assert sum(c.flops for c in costs) == pytest.approx(total, rel=1e-9)
    full, nbytes = mod.attention_cost(cfg, 2, "full_attention")
    assert full == 640.0 * 2 * 32 * (8192 * 8193 // 2) == pytest.approx(
        1.3745e12, rel=1e-3)
    assert nbytes == 2 * 8192 * 32 * (192 + 192 + 128 + 128) * 2
    eflops, ebytes = mod.experts_cost(cfg, 40960, 5)
    assert eflops == 40960 * 6.0 * 2048 * 768
    assert ebytes == 5 * 16 * 3 * 2048 * 768 * 2 + 40960 * (
        2048 * 2 + 2 * 768 * 4 + 768 * 2 + 2048 * 4)


def test_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(ln) for ln in f
                   if '"name": "JoyAI-LLM-Flash"' in ln)
    cfg = bench.load_json("configs", "joyai_llm_flash", (bench.HERE,))
    changed = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = next(c for c in json.load(f)["configs"]
                        if c["name"] == "joyai_llm_flash")
    assert declared["reduced"] == cfg["reduced"]
    assert declared["source"] == cfg["source"] == row["source_url"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] == len(cfg["experts_held"]) == 16
    assert cfg["router_width"] == cfg["published"]["n_routed_experts"] == 256
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
    attention = 2048 * 1536 + 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 \
        + 512 * 32 * 256 + 32 * 128 * 2048
    assert attention == 26_347_520
    outside = attention + 4096 + 2048 * 256 + 3 * 2048 * 768
    held, published = cfg["held"], cfg["published"]
    assert held["parameters_dense_layer"] == attention + 4096 + 3 * 2048 * 7168
    assert held["parameters_per_sparse_layer"] == outside + 16 * 3 * 2048 * 768
    assert published["parameters_per_sparse_layer"] == outside + 256 * 3 * 2048 * 768
    assert held["parameters_mtp_module"] == held["parameters_per_sparse_layer"] \
        + 2 * 2048 * 2048 + 3 * 2048
    assert held["parameters"] == held["parameters_dense_layer"] \
        + 4 * held["parameters_per_sparse_layer"] + held["parameters_mtp_module"] \
        + 2 * cfg["vocab_size"] * 2048 + 2048 == 680_439_808
    assert held["bytes_at_16_per_parameter"] == 16 * held["parameters"]
    assert published["parameters"] == 50_190_481_408
    for key in ("router_bias_update_rate", "auxiliary_loss", "mtp_module",
                "mtp_loss_weight", "initialisation", "optimizer",
                "records_per_step", "records"):
        assert key in cfg["assumed"], key
    for key in COMPARED | {"bias_count_slack"}:
        assert key in cfg["correct"]["reference"], key
    # and the program's own parameter tree holds exactly that many numbers
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import decoder_lm

    model = decoder_lm.from_config(parts_config(cfg))
    shapes = jax.eval_shape(
        lambda: (model.build(jax.random.PRNGKey(0), jax.ShapeDtypeStruct(
            (1, 256), jnp.int32)), model.get_parameters())[1])
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) == \
        held["parameters"]


def parts_config(cfg):
    return bench.load_module("configs", "joyai_llm_flash",
                             (bench.HERE,)).model_config(cfg)


@pytest.mark.parametrize("text,names,scope", [
    ("jit(train_step)/layer_1/block/attn/mla_proj/dot_general",
     scope_names.LATENT_SCOPES, "mla_proj"),
    ("jit(train_step)/layer_1/block/experts/moe_shared/dot_general",
     scope_names.LATENT_SCOPES, "moe_shared"),
    ("jit(train_step)/layer_1/block/experts/moe_shared/dot_general",
     scope_times.HYBRID_SCOPES, None),     # the shared expert is no `mlp`
    ("jit(train_step)/mtp/mtp/layer/block/attn/attn_full/flash_fwd",
     scope_names.LATENT_SCOPES, "attn_full"),
    ("jit(train_step)/mtp/mtp/layer/block/attn/attn_full/flash_fwd",
     ("mtp",), "mtp"),
    ("transpose(jvp(mtp))/lm_head/dot_general", ("mtp",), "mtp"),
    ("jit(train_step)/layer_0/block/mlp/mlp/dot_general",
     scope_times.HYBRID_SCOPES, "mlp"),
    ("jit(train_step)/layer_0/block/attn/attn_full/flash_fwd", ("mtp",), None),
])
def test_scope_of_an_ops_text(text, names, scope):
    assert scope_times.scope_of(text, names) == scope
