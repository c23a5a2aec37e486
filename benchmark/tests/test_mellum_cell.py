"""The language-model cell on the CPU: its driver end to end at a tiny fixture
configuration (the comparison with the reference included), the counting form
pinned at the cell's sizes, and the scope reader on a trace written here."""

import json
import os
import time

import pytest

from benchmark import run as bench
from benchmark.lib import flops, scopes, trace

ROOT = bench.ROOT
FIXTURES = os.path.join(bench.HERE, "tests", "fixtures")
TINY = "tiny_mellum.tiny_packed"
CELL = "mellum2_12b.packed8k"


def _rehearse(trace_on, capsys, **extra):
    rehearsal = {"platform": "cpu", **extra}
    result = bench.run_cell(TINY, 2**31 + 79, 1.0, trace_on,
                            t0=time.perf_counter(),
                            roots=(FIXTURES, bench.HERE), rehearsal=rehearsal)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return result, lines


def test_untraced_run_is_correct_and_compares_with_the_reference(capsys):
    result, lines = _rehearse(False, capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 4
    assert set(result["metrics"]) == {
        "setup_s", "train_records_per_s_per_chip", "train_step_ms_p95"}
    compared = next(ln for ln in lines if "reference_comparison" in ln)
    assert compared["broken"] == []
    assert set(compared["reference_comparison"]) == {
        "loss_abs", "logits_abs", "grad_rel_l2_head",
        "grad_rel_l2_first_router", "grad_rel_l2_worst", "pairs_local_rel",
        "load_max_over_mean_abs", "dropped_pairs"}
    for got in compared["reference_comparison"].values():
        assert got["value"] <= got["limit"]
    assert compared["counters"]["moe_dropped_pairs"] == 0
    assert any(ln.get("agrees") is True for ln in lines)


def test_a_tighter_limit_breaks_correct(capsys, tmp_path):
    with open(os.path.join(FIXTURES, "configs", "tiny_mellum.json")) as f:
        cfg = json.load(f)
    cfg["correct"]["reference"]["logits_abs"] = 0.0
    os.makedirs(tmp_path / "configs")
    (tmp_path / "configs" / "tiny_mellum.json").write_text(json.dumps(cfg))
    result = bench.run_cell(TINY, 11, 1.0, False, t0=time.perf_counter(),
                            roots=(str(tmp_path), FIXTURES, bench.HERE),
                            rehearsal={"platform": "cpu"})
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert result["correct"] is False
    assert next(ln for ln in lines if "broken" in ln and "agrees" not in ln
                and "reference_comparison" in ln)["broken"] == ["logits_abs"]


@pytest.mark.parametrize("stand_in,breaks", [
    # the nearest precision below the stated one, throughout
    ({"dtype": "bfloat16"}, "grad_rel_l2_head"),
    # planted faults: a window one short, one expert a token too few, the
    # neighbouring share's experts
    ({"sliding_window": 7}, "logits_abs"),
    ({"num_experts_per_tok": 1}, "pairs_local_rel"),
    ({"experts_held": [1, 2, 3, 4]}, "grad_rel_l2_worst"),
])
def test_a_lower_precision_or_a_fault_in_the_systems_place_is_not_correct(
        stand_in, breaks):
    parts = bench.load_cell(TINY, roots=(FIXTURES, bench.HERE))
    logged = []
    agrees = parts["config_module"].compare(
        parts["cfg"], parts["mix"], parts["generator"], 2**31 + 5,
        lambda **kw: logged.append(kw), stand_in=stand_in)
    assert agrees is False
    assert breaks in logged[-1]["broken"]
    assert logged[-1]["stand_in"] == stand_in


def test_the_stated_precision_is_told_from_the_next_lower_one():
    """What the cell's limits rest on, at a size the CPU can do: with
    bfloat16 operands and float32 sums stated, the system lies several times
    nearer the reference at that precision than the same equations in
    bfloat16 throughout do, in the loss-side gradient that crosses the
    fewest kernels (the toy fixture cannot show it: one swapped expert among
    its 128 routed pairs outweighs all rounding)."""
    parts = bench.load_cell(TINY, roots=(FIXTURES, bench.HERE))
    cfg = json.loads(json.dumps(parts["cfg"]))
    cfg.update(hidden_size=128, head_dim=32, num_attention_heads=4,
               num_key_value_heads=2, sliding_window=64, vocab_size=512,
               num_experts=4, router_width=8, num_experts_per_tok=2,
               moe_intermediate_size=64)
    cfg["dtypes"] = {"compute": "bfloat16", "activation": "float32"}
    cfg["deployment"]["record_tokens"] = 256
    mix = {**parts["mix"], "record_tokens": 256}
    for seed in (1, 2):
        read = {}
        for name, stand_in in (("system", None),
                               ("lower", {"dtype": "bfloat16"})):
            logged = []
            parts["config_module"].compare(
                cfg, mix, parts["generator"], seed,
                lambda **kw: logged.append(kw), block_q=128,
                stand_in=stand_in)
            assert logged[-1]["reference_operands"] == "bfloat16"
            read[name] = logged[-1]["reference_comparison"]
        head = "grad_rel_l2_head"
        assert read["lower"][head]["value"] > 3 * read["system"][head]["value"]
        assert read["system"][head]["value"] < 0.003


def test_traced_run_reports_what_a_cpu_trace_allows(capsys):
    r3 = trace.read_chrome_trace(os.path.join(
        ROOT, "bench_artifacts", "resnet50_b128_bf16act_s2d_trace.json.gz"))
    result, _ = _rehearse(True, capsys, device_kind="TPU v5 lite",
                          reduced=trace.reduce_events(r3))
    # the CPU's trace has no device plane, so the scope readers find nothing
    # and leave their metrics out; the counters and host metrics are there
    assert {"dispatch_ms.train", "compile_first_dispatch_s",
            "model_flops_util_pct.train", "device_idle_pct.train",
            "moe_load_max_over_mean.train"} <= set(result["metrics"])
    assert result["metrics"]["moe_load_max_over_mean.train"]["value"] >= 1.0
    assert result["metrics"]["model_flops_util_pct.train"]["value"] > 0


def test_traced_steps_records_reach_the_readers(capsys):
    """The experts' roofline sets the traced steps' device time against the
    traced steps' own pairs, not the window's."""
    parts = bench.load_cell(TINY, roots=(FIXTURES, bench.HERE))
    run = parts["driver"].run(
        parts["cell"], parts["cfg"], parts["config_module"], parts["mix"],
        parts["generator"], seed=13, seconds=1.0, trace=True,
        t0=time.perf_counter(), chips=1,
        scratch=os.path.join(ROOT, ".scratch", "benchmark"), on_tpu=False,
        log=lambda **kw: None)
    n = parts["mix"]["traced_steps"]
    numbers = [r["iteration"] for r in run.traced_steps]
    assert numbers == list(range(numbers[0], numbers[0] + n))
    assert not set(numbers) & {r["iteration"] for r in run.steps}
    assert scopes.counter_mean(run, "moe_pairs_local", traced=True) == \
        sum(r["moe_pairs_local"] for r in run.traced_steps) / n


def test_counting_form_is_pinned_at_the_cells_sizes():
    parts = bench.load_cell(CELL)
    cfg, mod = parts["cfg"], parts["config_module"]
    batch = cfg["deployment"]["batch_per_chip"]
    assert mod.visible_pairs(8192) == 33_558_528
    assert mod.visible_pairs(8192, 1024) == 7_864_832
    total = sum(2.0 * m * k * n for _, m, k, n in mod.products(cfg, batch))
    assert total == pytest.approx(8.154e12, rel=2e-4)
    # and the walk over the counting form's jaxpr counts the same
    import jax
    import jax.numpy as jnp

    shapes = [(m, k, n) for _, m, k, n in mod.products(cfg, batch)]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for m, k, n in shapes for s in ((m, k), (k, n))]
    costs = flops.matmul_costs(
        lambda *a: [jnp.dot(x, w) for x, w in zip(a[::2], a[1::2])], *args)
    assert sum(c.flops for c in costs) == pytest.approx(total, rel=1e-9)
    full, _ = mod.attention_cost(cfg, batch, "full_attention")
    sliding, _ = mod.attention_cost(cfg, batch, "sliding_attention")
    assert full == pytest.approx(1.0997e12, rel=1e-3)
    assert sliding / full == pytest.approx(0.2344, rel=1e-3)
    per_layer, _ = mod.experts_cost(cfg, 2 * 8192 * 2, 1)
    assert per_layer == pytest.approx(0.4059e12, rel=1e-3)


def test_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(ln) for ln in f if "Mellum2-12B" in ln)
    cfg = bench.load_json("configs", "mellum2_12b", (bench.HERE,))
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["source"] == row["source_url"]
    held = cfg["held"]
    assert held["parameters"] == 4 * held["parameters_per_layer"] \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    assert held["bytes_at_16_per_parameter"] == 16 * held["parameters"]


@pytest.mark.parametrize("text,scope", [
    ("jit(train_step)/layer_0/block/attn/attn_window/flash_fwd", "attn_window"),
    ("transpose(jvp(layer_3))/block/attn/attn_full/flash_bwd_dkv", "attn_full"),
    ("jit(train_step)/layer_1/block/experts/moe_experts/gmm", "moe_experts"),
    ("jit(train_step)/layer_1/block/experts/moe_route/sort", "moe_route"),
    ("jit(train_step)/embedding_lookup", None),
    ("fusion.12 loop fusion", None),
])
def test_scope_of_an_ops_text(text, scope):
    assert scopes.scope_of(text) == scope


def test_scope_reader_finds_nothing_without_a_device(tmp_path):
    assert scopes.read(str(tmp_path)) is None
    assert scopes.read(None) is None
