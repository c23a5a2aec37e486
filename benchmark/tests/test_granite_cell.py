"""The state-space hybrid's cell on the CPU: its driver end to end at a tiny
fixture configuration (the comparison with the sequential-recurrence
reference included), the counting form and ``ssd_cost`` pinned at the cell's
sizes against hand numbers, the planted faults and the lower-precision
control through ``compare`` at hidden 128, and the named-scope reader on a
trace written here."""

import json
import os
import time

import pytest

from benchmark import run as bench
from benchmark.lib import flops, scope_times, trace

ROOT = bench.ROOT
FIXTURES = os.path.join(bench.HERE, "tests", "fixtures")
TINY = "tiny_granite.tiny_packed"
CELL = "granite_4_0_h_micro.packed8k"
COMPARED = {"loss_abs", "logits_abs", "grad_rel_l2_embed",
            "grad_rel_l2_first_A_log", "grad_rel_l2_first_dt_bias",
            "grad_rel_l2_first_conv", "grad_rel_l2_worst",
            "log_decay_min_rel", "state_rms_rel"}


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
    assert names == parts["cell"]["per_layer"] and len(names) == 21
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = json.load(f)
    for m in declared["per_layer"]:
        reported = m["name"] in names
        listed = CELL in m.get("workloads", [CELL])
        assert reported == listed, m["name"]
    reader = {r.NAME: r for r in parts["readers"]}
    for m in declared["per_layer"][-5:]:
        r = reader[m["name"]]
        assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
        assert m["workloads"] == [CELL]


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
    assert compared["counters"]["ssm_log_decay_min"] < 0
    assert compared["counters"]["ssm_state_rms"] > 0
    assert any(ln.get("agrees") is True for ln in lines)


def _hidden_128(parts):
    """What the limits rest on, at a size the CPU can do: hidden 128, T 256
    in chunks of 64, bfloat16 operands stated."""
    cfg = json.loads(json.dumps(parts["cfg"]))
    cfg.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
               mamba_n_heads=8, mamba_d_head=32, mamba_d_state=32,
               mamba_chunk_size=64, shared_intermediate_size=256,
               vocab_size=512, attention_multiplier=1 / 32)
    cfg["dtypes"] = {"compute": "bfloat16", "activation": "float32"}
    cfg["deployment"]["record_tokens"] = 256
    cfg["correct"]["reference"] = {
        "loss_abs": 1e-3, "logits_abs": 0.05, "grad_rel_l2_embed": 0.01,
        "grad_rel_l2_first_A_log": 0.06, "grad_rel_l2_first_dt_bias": 0.06,
        "grad_rel_l2_first_conv": 0.03, "grad_rel_l2_worst": 0.08,
        "log_decay_min_rel": 1e-3, "state_rms_rel": 5e-3}
    return cfg, {**parts["mix"], "record_tokens": 256}


@pytest.fixture(scope="module")
def readings():
    """``compare`` at hidden 128: the system, the lower-precision control and
    the three planted faults in its place."""
    parts = bench.load_cell(TINY, roots=(FIXTURES, bench.HERE))
    cfg, mix = _hidden_128(parts)
    out = {}
    for name, stand_in in (
            ("system", None), ("lower", {"dtype": "bfloat16"}),
            ("zero_state", {"state_carried": False}),
            ("score_scale", {"attention_multiplier": 1 / 32 ** 0.5}),
            ("residual", {"residual_multiplier": 1.0})):
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


@pytest.mark.parametrize("name", ["lower", "zero_state", "score_scale",
                                  "residual"])
def test_a_lower_precision_or_a_fault_in_the_systems_place_is_not_correct(
        readings, name):
    agrees, log = readings[name]
    assert agrees is False and log["broken"]
    system = readings["system"][1]["reference_comparison"]
    for k in log["broken"]:
        assert log["reference_comparison"][k]["value"] > 2 * system[k]["value"]


def test_a_tighter_limit_breaks_correct(capsys, tmp_path):
    with open(os.path.join(FIXTURES, "configs", "tiny_granite.json")) as f:
        cfg = json.load(f)
    cfg["correct"]["reference"]["logits_abs"] = 0.0
    os.makedirs(tmp_path / "configs")
    (tmp_path / "configs" / "tiny_granite.json").write_text(json.dumps(cfg))
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
    # and leave their metrics out without raising; the host metrics are there
    assert {"dispatch_ms.train", "compile_first_dispatch_s",
            "model_flops_util_pct.train", "device_idle_pct.train",
            "peak_hbm_gib"} <= set(result["metrics"])
    assert not {"ssm_scan_ms.train", "ssm_scan_roofline.train", "mlp_ms.train",
                "attn_full_ms.train"} & set(result["metrics"])
    assert result["metrics"]["model_flops_util_pct.train"]["value"] > 0


class _Run:
    """What a reader sees of a run, over a rehearsed table of scope times."""

    def __init__(self, parts, seconds):
        from benchmark.lib import scopes
        from benchmark.lib.peaks import peaks

        self.forward = parts["config_module"].build
        self.peaks = peaks("TPU v5 lite")
        self.logged = []
        self.log = lambda **kw: self.logged.append(kw)
        self.trace_dir = None
        table = scopes.ScopeTimes(6, seconds, {})
        self._named_scope_times = {scope_times.HYBRID_SCOPES: table}
        self._scope_times = table


def test_every_scope_reader_prints_on_a_rehearsed_trace():
    """The five new readers and the two attention ones over a table of device
    seconds by scope, as a chip's trace would give: each prints, and the
    roofline is the hand number over the scope's time."""
    parts = bench.load_cell(CELL)
    cfg, mod = parts["cfg"], parts["config_module"]
    seconds = {"ssm_scan": 0.0684, "ssm_conv": 0.010, "ssm_proj": 0.120,
               "mlp": 0.300, "attn_full": 0.020}
    run = _Run(parts, seconds)
    run.forward = lambda: None
    run.forward.ssd_cost = lambda: mod.ssd_cost(cfg, 1)
    run.forward.attention_cost = lambda kind: mod.attention_cost(cfg, 1, kind)
    run.forward.layer_kinds = mod._layer_kinds(cfg)
    got = {r.NAME: r.read(run) for r in parts["readers"]
           if r.NAME.startswith(("ssm_", "mlp_", "attn_"))}
    assert got["ssm_scan_ms.train"] == pytest.approx(68.4)
    assert got["ssm_conv_ms.train"] == pytest.approx(10.0)
    assert got["ssm_proj_ms.train"] == pytest.approx(120.0)
    assert got["mlp_ms.train"] == pytest.approx(300.0)
    assert got["attn_full_ms.train"] == pytest.approx(20.0)
    # 9 layers x 3 x 207.6 MB / 819 GB/s = 6.84 ms: memory-bound
    assert got["ssm_scan_roofline.train"] == pytest.approx(10.0, rel=2e-3)
    assert any(ln.get("ssm_scan_roofline_bound") == "memory"
               and ln["ssm_scan_least_ms_per_step"] == pytest.approx(6.84, rel=2e-3)
               for ln in run.logged)
    # one attention layer: 3 x 274.9 GFLOP / 197 TFLOP/s = 4.19 ms of 20
    assert got["attn_roofline.train"] == pytest.approx(20.93, rel=2e-3)


def test_readers_leave_their_metric_out_where_the_program_has_no_such_scope():
    parts = bench.load_cell(CELL)
    run = _Run(parts, {"attn_full": 0.02})
    run.forward = lambda: None          # the parent's forward has no ssd_cost
    run.forward.layer_kinds = []
    for r in parts["readers"]:
        if r.NAME.startswith(("ssm_", "mlp_")):
            assert r.read(run) is None


def test_counting_form_and_ssd_cost_against_hand_numbers():
    parts = bench.load_cell(CELL)
    cfg, mod = parts["cfg"], parts["config_module"]
    assert cfg["deployment"]["batch_per_chip"] == 1
    assert mod.chunk_pairs(8192, 256) == 8192 * 257 // 2 == 1_052_672
    assert mod.chunk_pairs(300, 256) == 256 * 257 // 2 + 44 * 45 // 2
    total = sum(2.0 * m * k * n for _, m, k, n in mod.products(cfg, 1))
    assert total == pytest.approx(13.16e12, rel=5e-4)
    share = lambda *keys: sum(  # noqa: E731
        2.0 * m * k * n for name, m, k, n in mod.products(cfg, 1)
        if name.split(".")[-1] in keys) / total
    assert share("mlp_in", "mlp_out") == pytest.approx(0.63, abs=0.005)
    assert share("in_proj", "out_proj") == pytest.approx(0.29, abs=0.005)
    assert share("scan_cb", "scan_lx", "scan_states", "scan_cs") == \
        pytest.approx(0.018, abs=0.001)
    assert share("qk", "pv") == pytest.approx(0.02, abs=0.002)
    assert share("head") == pytest.approx(0.03, abs=0.003)
    # and the walk over the counting form's jaxpr counts the same
    import jax
    import jax.numpy as jnp

    shapes = [(m, k, n) for _, m, k, n in mod.products(cfg, 1)]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for m, k, n in shapes for s in ((m, k), (k, n))]
    costs = flops.matmul_costs(
        lambda *a: [jnp.dot(x, w) for x, w in zip(a[::2], a[1::2])], *args)
    assert sum(c.flops for c in costs) == pytest.approx(total, rel=1e-9)
    scan_flops, scan_bytes = mod.ssd_cost(cfg, 1)
    assert scan_flops == pytest.approx(26.07e9, rel=2e-4)
    assert scan_bytes == pytest.approx(207.6e6, rel=2e-4)
    assert scan_flops == sum(
        2.0 * m * k * n for name, m, k, n in mod.products(cfg, 1)
        if name.startswith("l0.scan_"))
    assert mod.attention_cost(cfg, 1, "mamba") == (0.0, 0.0)
    full, nbytes = mod.attention_cost(cfg, 1, "attention")
    assert full == pytest.approx(0.2749e12, rel=1e-3)
    assert nbytes == 8192 * 64 * 2 * 40 * 2


def test_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(ln) for ln in f
                   if '"name": "granite-4.0-h-micro"' in ln)
    cfg = bench.load_json("configs", "granite_4_0_h_micro", (bench.HERE,))
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers", "vocab_size"}
    assert cfg["source"] == row["source_url"]
    assert cfg["layer_types"][:10] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    held = cfg["held"]
    assert held["parameters"] == 9 * held["parameters_per_mamba_layer"] \
        + held["parameters_per_attention_layer"] \
        + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"] \
        == 772_160_448
    assert held["bytes_at_16_per_parameter"] == 16 * held["parameters"]
    assert cfg["published"]["parameters"] == 3_191_396_096
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
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


@pytest.mark.parametrize("text,scope", [
    ("jit(train_step)/layer_0/block/ssm/ssm_scan/dot_general", "ssm_scan"),
    ("transpose(jvp(layer_3))/block/ssm/ssm_proj/dot_general", "ssm_proj"),
    ("jit(train_step)/layer_1/block/ssm/ssm_conv/mul", "ssm_conv"),
    ("jit(train_step)/layer_5/block/mlp/mlp/dot_general", "mlp"),
    ("jit(train_step)/layer_5/block/attn/attn_full/flash_fwd", None),
    ("jit(train_step)/mlperf_thing", None),
    ("fusion.12 loop fusion", None),
])
def test_scope_of_an_ops_text(text, scope):
    assert scope_times.scope_of(text, scope_times.HYBRID_SCOPES) == scope


def test_named_scope_reader_finds_nothing_without_a_device(tmp_path):
    assert scope_times.read(str(tmp_path), scope_times.HYBRID_SCOPES) is None
    assert scope_times.read(None, scope_times.HYBRID_SCOPES) is None
