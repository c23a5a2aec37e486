"""Contracts of the chip bring-up: nothing on the main path may hide the
device. The smoke demands the chip, kernel gates raise the compiler's error
instead of rerouting to XLA, interpret mode is refused on the TPU, and an
unknown accelerator has no guessed peak.

The ``tpu`` backend is faked by patching ``jax.default_backend`` — the one
thing every gate reads — so the TPU-side branches run on the CPU host.
"""

import os
import subprocess
import sys
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.ops import fused_common
from bigdl_tpu.utils import compat
from bigdl_tpu.utils.engine import Engine

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def fake_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


class _MosaicRefused(RuntimeError):
    pass


def _refuse(*_a, **_k):
    raise _MosaicRefused("Mosaic failed to compile TPU kernel")


# ------------------------------------------------------------- the smoke
def test_chip_smoke_refuses_the_cpu(tmp_path):
    """The sandbox exports JAX_PLATFORMS=cpu; a smoke that inherits that and
    passes is the failure the bring-up exists to prevent."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=str(tmp_path),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "platform: cpu" in proc.stdout
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line on a failed run


@pytest.mark.slow  # ~50 s of ResNet-18 compiles: run it before a chip call,
def test_chip_smoke_phases_rehearse_at_tiny_size(tmp_path, monkeypatch):  # not in tier-1
    """The phases after the device check, end to end on the virtual CPU mesh:
    the control flow the chip run takes (optimize() with telemetry, ZeRO-1
    over every device, every kernel against its reference in interpret mode,
    concurrent serving bit-equal to the serial Predictor, the bitwise
    read-back of device batches made from recycled host buffers).

        JAX_PLATFORMS=cpu python -m pytest tests/test_chip_contracts.py -m slow
    """
    chip_smoke = _chip_smoke()
    monkeypatch.setattr(chip_smoke, "LAST_RUN", str(tmp_path / "last.json"))
    prev = Engine.compute_dtype(), Engine.activation_dtype()
    try:
        # before phase B sets bf16 operands: XLA:CPU has no bf16 x bf16 -> f32
        # product, which GroupedQueryAttention's projections are on the chip
        chip_smoke.check_flash_remat(t=128, d=16, n=1, heads=2)
        chip_smoke.check_ssd_scan(t=48, heads=4, head_dim=8, state=16,
                                  chunk=16, n=2)
        chip_smoke.check_ssd_scan(t=48, heads=8, head_dim=8, state=16,
                                  chunk=16, n=2, groups=4)
        chip_smoke.check_routed_experts(tokens=1024, hidden=32, ffn=16,
                                        n_experts=16, n_held=2, top_k=2)
        assert all(chip_smoke.check_relu2_tilings(
            rows=256, hidden=32, ffn=24, held=4, tiles=(8, 24), reps=1).values())
        model, rec = chip_smoke.phase_train(
            depth=18, classes=10, image=32, batch=4, iters=6)
        assert rec["compile_s"] > 0
        chip_smoke.phase_distri(
            depth=18, classes=10, image=32, batch_per_chip=2, iters=4)
        chip_smoke.check_flash(t=128, d=16, n=1, h=2)
        chip_smoke.check_flash_latent(t=128, d=24, d_v=16, n=1, h=2)
        chip_smoke.check_fused(rows=24, hidden=128, conv_shape=(2, 4, 6, 6))
        chip_smoke.check_transformer_step(
            t=32, batch=2, vocab=64, hidden=32, heads=2, ffn=64, layers=1,
            iters=4)
        # phase C left the Engine mesh over all 8 virtual devices, so the
        # Predictor shards its batch across them — as on a four-chip host
        chip_smoke.phase_serving(model, image=32, batch=8, requests=8,
                                 clients=2)
        chip_smoke.phase_readback(image=16, batch_per_chip=8, batches=4,
                                  epochs=3, hold=2, step_s=0.005)
    finally:
        Engine.reset()
        Engine.set_compute_dtype(prev[0])
        Engine.set_activation_dtype(prev[1])


def _chip_smoke():
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    return chip_smoke


def test_smoke_demands_the_mosaic_call_only_where_mosaic_runs(fake_tpu):
    cs = _chip_smoke()
    text = 'stablehlo.custom_call @tpu_custom_call(%a) ... @tpu_custom_call(%b)'
    assert cs.assert_mosaic(text, "two kernels") == 2
    with pytest.raises(AssertionError, match="no tpu_custom_call"):
        cs.assert_mosaic("stablehlo.add %a, %b", "interpreted expansion")


def test_smoke_parity_rule_is_the_kernel_suites():
    """|Δ| ≤ tol·(1 + max|ref|) on every leaf, and every leaf finite."""
    cs = _chip_smoke()
    ref = {"out": jnp.asarray([1.0, -3.0]), "grad": jnp.asarray([0.5])}
    cs._close({"out": jnp.asarray([1.1, -3.0]), "grad": jnp.asarray([0.5])},
              ref, 0.05, "within 0.05*(1+3)")
    with pytest.raises(AssertionError, match="max"):
        cs._close({"out": jnp.asarray([1.3, -3.0]), "grad": ref["grad"]},
                  ref, 0.05, "outside")
    with pytest.raises(AssertionError):
        cs._close({"out": jnp.asarray([jnp.nan, -3.0]), "grad": ref["grad"]},
                  ref, 0.05, "non-finite")


# ------------------------------------------------------------ compile cache
def test_unwritable_cache_dir_names_the_way_out(tmp_path):
    blocker = tmp_path / "a_file"
    blocker.write_text("not a directory")
    prev = jax.config.jax_compilation_cache_dir
    try:
        with pytest.raises(RuntimeError, match="JAX_COMPILATION_CACHE_DIR"):
            compat.enable_persistent_compilation_cache(str(blocker / "cache"))
    finally:
        if prev:
            compat.enable_persistent_compilation_cache(prev)


def test_ensure_compilation_cache_applies_the_rule_once(tmp_path, monkeypatch):
    default = str(tmp_path / "jax_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compat, "DEFAULT_COMPILE_CACHE_DIR", default)
    prev = Engine.compilation_cache_dir()
    monkeypatch.setattr(Engine._state, "compilation_cache_dir", None)
    calls = []
    real = compat.enable_persistent_compilation_cache
    monkeypatch.setattr(compat, "enable_persistent_compilation_cache",
                        lambda *a: calls.append(a) or real(*a))
    try:
        assert Engine.ensure_compilation_cache() == default
        assert Engine.ensure_compilation_cache() == default  # no second apply
        assert calls == [()]
        assert jax.config.jax_compilation_cache_dir == default
        assert os.path.isdir(default)
        # persist-everything thresholds ride along
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_enable_xla_caches == ""
    finally:
        if prev:
            real(prev)


# ------------------------------------------- what the first chip run found
def test_one_device_zero1_step_compiles_once():
    """On a one-chip host the ZeRO-1 mesh has one device, where jax hands the
    P('data') slot vectors back spelled P(): committed under the other
    spelling, call 2 missed call 1's executable and the whole SPMD step
    compiled twice (chip run, PR 21). Momentum makes the slots exist."""
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.obs import Telemetry
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer

    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, 6)).astype(np.float32)
    y = rng.integers(0, 2, 32)
    try:
        Engine.init(devices=jax.devices()[:1])
        opt = DistriOptimizer(
            nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 2),
                          nn.LogSoftMax()),
            DataSet.distributed(DataSet.array(x, y, batch_size=8), 1),
            nn.ClassNLLCriterion(), parameter_sync="sharded")
        opt.set_optim_method(SGD(learningrate=0.1, momentum=0.9))
        opt.set_end_when(Trigger.max_iteration(4))
        tel = Telemetry(exporters=[])
        opt.set_telemetry(tel)
        opt.optimize()
        assert tel.compile_count == 1
        assert opt._jit_step._cache_size() == 1
        tel.close()
    finally:
        Engine.reset()


def test_time_distributed_criterion_is_one_vectorized_trace():
    """Same per-step semantics as the reference's loop over time — and a
    program whose size does not grow with T (the unrolled LM step at T=2048
    took XLA:TPU ~12 minutes to compile)."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.standard_normal((3, 7, 5)), jnp.float32)
    target = jnp.asarray(rng.integers(0, 5, (3, 7)))
    for size_average in (False, True):
        crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(),
                                           size_average=size_average)
        inner = nn.CrossEntropyCriterion()
        loop = sum(inner._apply(logits[:, t], target[:, t]) for t in range(7))
        want = loop / 7 if size_average else loop
        np.testing.assert_allclose(crit._apply(logits, target), want,
                                   rtol=1e-6)
        g = jax.grad(lambda z: crit._apply(z, target))(logits)
        assert g.shape == logits.shape and bool(jnp.all(jnp.isfinite(g)))

    def n_eqns(t):
        z = jnp.zeros((2, t, 5))
        return len(jax.make_jaxpr(
            lambda z: crit._apply(z, jnp.zeros((2, t), jnp.int32)))(z).eqns)

    assert n_eqns(4) == n_eqns(64)


# ---------------------------------------------------------- kernel gates
def test_flash_auto_gate_raises_the_compilers_error(fake_tpu, monkeypatch):
    import bigdl_tpu.ops as ops
    from bigdl_tpu.nn.attention import scaled_dot_product_attention

    monkeypatch.setattr(ops, "flash_attention", _refuse)
    q = jnp.zeros((1, 1, 1024, 8), jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a warning-and-reroute would raise too
        with pytest.raises(_MosaicRefused):
            scaled_dot_product_attention(q, q, q, causal=True)  # impl="auto"
    # below the shape threshold the gate picks dense: the kernel is never
    # reached, whatever state Mosaic is in
    short = jnp.zeros((1, 1, 64, 8), jnp.float32)
    assert scaled_dot_product_attention(short, short, short).shape == short.shape


def test_maxpool_pallas_gate_raises_the_compilers_error(fake_tpu, monkeypatch):
    import bigdl_tpu.ops.maxpool as M

    monkeypatch.setenv("BIGDL_MAXPOOL_GRAD_IMPL", "pallas")
    monkeypatch.setattr(M, "_maxpool_grad_nchw", _refuse)
    x = jnp.arange(16.0).reshape(1, 1, 4, 4)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with pytest.raises(_MosaicRefused):
            jax.grad(lambda x: jnp.sum(M.maxpool2d(
                x, (2, 2), (2, 2), ((0, 0), (0, 0)))))(x)


def test_fused_kernel_gate_compiles_or_raises_on_the_tpu(fake_tpu):
    """With the switch on and the backend reporting tpu, the fused LayerNorm
    goes to the real Pallas compile path — which this CPU host cannot serve,
    so the lowering error surfaces. It is not swallowed into the jnp path."""
    ln = nn.LayerNormalization(128)
    params = {"weight": jnp.ones(128), "bias": jnp.zeros(128)}
    Engine.set_fused_kernels(True)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            with pytest.raises(Exception, match="(?i)interpret|mosaic|tpu"):
                jax.jit(lambda x: ln.apply(params, {}, x, training=False,
                                           rng=None)[0])(jnp.ones((8, 128)))
    finally:
        Engine.set_fused_kernels(False)


# --------------------------------------------------------- interpret mode
def _double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def _launch(**kw):
    x = jnp.ones((8, 128), jnp.float32)
    return compat.pallas_call(
        _double, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype), **kw)(x)


def test_interpret_mode_is_how_the_cpu_runs_kernels():
    assert compat.pallas_interpret_default() is True
    np.testing.assert_array_equal(np.asarray(_launch()), 2.0)


def test_interpret_requested_on_the_tpu_is_an_error(fake_tpu):
    with pytest.raises(RuntimeError, match="interpret mode requested on the tpu"):
        _launch(interpret=True)


def test_interpret_env_override_on_the_tpu_is_an_error(fake_tpu, monkeypatch):
    monkeypatch.setenv("BIGDL_PALLAS_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="interpret mode requested on the tpu"):
        _launch()


# ------------------------------------------------------------------ peaks
def test_unknown_accelerator_has_no_guessed_peak():
    with pytest.raises(ValueError, match="TPU v9"):
        compat.device_peaks("TPU v9")
    assert compat.device_peaks("TPU v5 lite").flops == 197e12
    # the CPU backend still yields None: tier-1 and obs/perf.py read it as
    # "no MFU", never as an error
    assert compat.device_peaks() is None
    assert compat.device_peaks("cpu") is None


# ------------------------------------------------------------- block sizing
@pytest.mark.parametrize("itemsize,sublane", ((4, 8), (2, 16), (1, 32)))
def test_block_rows_are_whole_sublane_tiles(itemsize, sublane):
    """bf16 packs 16 rows to a tile, 8-bit packs 32; a block is never a
    fraction of a tile — not for short inputs (padded up) and not for rows so
    wide that the VMEM budget alone would ask for fewer than one tile."""
    for n_rows, row_bytes in ((5, 512), (1000, 8192), (4096, 1 << 20),
                              (37, 12544)):
        br = fused_common.block_rows(n_rows, row_bytes, itemsize)
        assert br >= sublane and br % sublane == 0
        assert br <= max(sublane, 1024)
