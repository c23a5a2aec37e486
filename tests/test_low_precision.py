"""Low-precision flat path of the ZeRO-1 sharded step: compressed gradient
collectives with error feedback + bf16 training state (docs/performance.md
low-precision section).

Locks the contract at four levels:

* **capability level** — the float8 availability shim
  (``utils/compat.probe_float8`` / ``resolve_precision_dtype``): typed probe,
  clean ``ValueError`` (never an import crash) on an unsupported stack;
* **math level** — stochastic rounding is unbiased and step-deterministic,
  the compressor's quantize→dequantize round trip is segment-scale-exact,
  and the error-feedback residual is exactly the untransmitted remainder;
* **program level** — the lowered ZeRO-1 sharded step's gradient-exchange
  collective operand bytes drop ≥2× (bf16) and ≥3.5× (fp8/int8) versus the
  f32 baseline, while the default (no-policy) program is byte-for-byte the
  pre-policy program;
* **run level** — trajectory-tolerance fits on the 8-device mesh
  (compressed loss curves within bound of the f32 baseline; error feedback
  ON strictly closer than OFF in the same test), exactly-1-compile ragged
  fits with compression + EF, retry-reuses-cached-step, checkpoint
  round-trips quantized↔unquantized, every other path refusing a policy,
  and the GSPMD/hybrid health path localizing the poisoned mesh shard.
"""

import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.dataset import AbstractDataSet, MiniBatch
from bigdl_tpu.obs import HealthConfig, Telemetry
from bigdl_tpu.optim import Adam, LocalOptimizer, SGD, Trigger
from bigdl_tpu.optim.quantization import (
    LowPrecisionPolicy,
    StatePrecision,
    stochastic_round,
)
from bigdl_tpu.parallel.compression import GradCompressor
from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel.parameter import FlatParameter
from bigdl_tpu.obs.profiler import collective_bytes
from bigdl_tpu.resilience import FailurePolicy
from bigdl_tpu.utils import compat
from bigdl_tpu.utils.random import RandomGenerator

_tm = jax.tree_util.tree_map

_spec = importlib.util.spec_from_file_location(
    "obs_report",
    Path(__file__).resolve().parent.parent / "tools" / "obs_report.py",
)
obs_report = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = obs_report
_spec.loader.exec_module(obs_report)


@pytest.fixture(autouse=True)
def _engine():
    """The mesh-test convention (tests/test_distri_optimizer.py): init the
    8-device engine for this file, and RESET on teardown so later files
    (e.g. serving tests with small batch sizes) see an uninitialized
    engine again."""
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    Engine.init()
    assert Engine.device_count() == 8
    yield
    Engine.reset()


def _problem(n=64, d=6, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.integers(0, classes, n)
    return x, y


def _model(d=6, classes=3, hidden=24):
    return nn.Sequential(
        nn.Linear(d, hidden), nn.Tanh(),
        nn.Linear(hidden, hidden), nn.Tanh(),
        nn.Linear(hidden, classes), nn.LogSoftMax(),
    )


def _leaves(params):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(params)]


def _finite(params) -> bool:
    return all(np.isfinite(l).all() for l in _leaves(params))


# --------------------------------------------------------------------------
# capability level: the float8 shim (utils/compat)
# --------------------------------------------------------------------------

class TestFloat8Shim:
    def test_probe_available_on_this_stack(self):
        support = compat.probe_float8()
        assert support.available, support.reason
        assert set(support.dtypes) == {"float8_e4m3fn", "float8_e5m2"}

    def test_resolver_spellings(self):
        assert compat.resolve_precision_dtype(None) is None
        assert compat.resolve_precision_dtype("bfloat16") == jnp.bfloat16
        assert compat.resolve_precision_dtype("int8") == jnp.int8
        assert (
            compat.resolve_precision_dtype("float8_e4m3")
            == jnp.float8_e4m3fn
        )
        assert (
            compat.resolve_precision_dtype("float8_e5m2") == jnp.float8_e5m2
        )

    def test_unknown_name_raises(self):
        with pytest.raises(ValueError, match="comms_dtype"):
            compat.resolve_precision_dtype("float4_nonsense")

    def test_unsupported_stack_is_a_clean_valueerror(self, monkeypatch):
        """The other probe branch: a stack without float8 must surface as a
        typed ValueError carrying the probe's reason — at the POLICY surface
        (optimizer construction), never an AttributeError mid-trace."""
        monkeypatch.setattr(
            compat, "_float8_probe_cache",
            compat.Float8Support(False, reason="simulated: no ml_dtypes"),
        )
        with pytest.raises(ValueError, match="simulated: no ml_dtypes"):
            compat.resolve_precision_dtype("float8_e4m3")
        x, y = _problem(n=16)
        with pytest.raises(ValueError, match="float8"):
            LocalOptimizer(
                _model(), DataSet.array(x, y, batch_size=8),
                nn.ClassNLLCriterion(), comms_dtype="float8_e5m2",
            )

    def test_bfloat16_policy_survives_unsupported_fp8_stack(self, monkeypatch):
        monkeypatch.setattr(
            compat, "_float8_probe_cache",
            compat.Float8Support(False, reason="simulated"),
        )
        pol = LowPrecisionPolicy(comms_dtype="bfloat16")
        assert pol.active and pol.comms_dtype == jnp.dtype(jnp.bfloat16)


# --------------------------------------------------------------------------
# math level: stochastic rounding + the compressor round trip
# --------------------------------------------------------------------------

class TestStochasticRounding:
    def test_bf16_unbiased(self):
        # a value exactly between two bf16 neighbours must round up ~half
        # the time: the bit-trick SR is exact, so the mean converges to x
        x = jnp.full((200_000,), 1.0 + 2.0 ** -10, jnp.float32)
        v = stochastic_round(x, jnp.bfloat16, jax.random.PRNGKey(0))
        assert v.dtype == jnp.bfloat16
        mean = float(jnp.mean(v.astype(jnp.float32)))
        assert abs(mean - (1.0 + 2.0 ** -10)) < 2e-4, mean

    def test_bf16_exact_values_unperturbed(self):
        x = jnp.asarray([0.0, 1.0, -2.5, 1024.0], jnp.float32)  # bf16-exact
        v = stochastic_round(x, jnp.bfloat16, jax.random.PRNGKey(1))
        np.testing.assert_array_equal(
            np.asarray(v.astype(jnp.float32)), np.asarray(x)
        )

    def test_deterministic_per_key(self):
        x = jnp.linspace(-3.0, 3.0, 1024, dtype=jnp.float32)
        a = stochastic_round(x, jnp.bfloat16, jax.random.PRNGKey(7))
        b = stochastic_round(x, jnp.bfloat16, jax.random.PRNGKey(7))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fp8_never_mints_nan_at_the_format_max(self):
        # dithering past the fp8 max would cast to NaN (no inf in e4m3fn);
        # the saturating clip keeps the edge finite
        x = jnp.full((4096,), 448.0, jnp.float32)
        v = stochastic_round(x, jnp.float8_e4m3fn, jax.random.PRNGKey(3))
        assert np.isfinite(np.asarray(v.astype(jnp.float32))).all()

    def test_f32_identity(self):
        x = jnp.asarray([1.1, 2.2], jnp.float32)
        assert stochastic_round(x, jnp.float32, jax.random.PRNGKey(0)) is x


def _exchange(comp, g, err, want_stats):
    """``GradCompressor.exchange_sharded`` on a one-device mesh: the summed
    owned shard is the whole dequantized gradient, and the residual row is
    the whole untransmitted remainder."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("d",))

    def body(g, err):
        me = jax.lax.axis_index("d")
        used, new_err, stats = comp.exchange_sharded(
            g, err, "d", 1, me, want_stats=want_stats)
        return used, None if new_err is None else new_err[0], stats

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=P(), check_vma=False))(g, err)


class TestCompressorMath:
    def _codec(self, seed=0, n_shards=1):
        rng = np.random.default_rng(seed)
        tree = {
            "a": {"weight": jnp.asarray(rng.standard_normal((16, 8)) * 5.0,
                                        jnp.float32)},
            "b": {"bias": jnp.asarray(rng.standard_normal((7,)) * 0.01,
                                      jnp.float32)},
        }
        return FlatParameter(tree, n_shards), tree

    def test_int8_round_trip_is_segment_scale_exact(self):
        fp, tree = self._codec()
        comp = GradCompressor(
            fp, LowPrecisionPolicy(comms_dtype="int8", error_feedback=False)
        )
        g = jax.jit(fp.flatten)(tree)
        used, err, _ = _exchange(comp, g, None, want_stats=False)
        # per-segment amax/127 grid: every element within half a step of its
        # OWN segment's scale (the big and tiny segments each keep their
        # resolution — the point of per-segment scales)
        seg = fp.segment_ids()
        scales = np.zeros(len(fp.sizes) + 1, np.float32)
        gnp = np.asarray(g)
        for s in range(len(fp.sizes)):
            vals = gnp[seg == s]
            scales[s] = np.abs(vals).max() / 127.0
        err_abs = np.abs(np.asarray(used) - gnp)
        assert (err_abs <= scales[seg][: len(gnp)] * 0.5 + 1e-12).all()
        assert err is None  # EF residual only materializes when requested

    def test_error_feedback_residual_is_the_untransmitted_remainder(self):
        fp, tree = self._codec()
        comp = GradCompressor(
            fp, LowPrecisionPolicy(comms_dtype="int8", error_feedback=True)
        )
        g = jax.jit(fp.flatten)(tree)
        err0 = jnp.zeros((fp.padded_total,), jnp.float32)
        used, err1, _ = _exchange(comp, g, err0, want_stats=False)
        np.testing.assert_allclose(
            np.asarray(used + err1), np.asarray(g), rtol=0, atol=1e-6
        )
        # second step recycles the residual: transmitted + new residual
        # still accounts for EVERY gradient bit ever produced
        used2, err2, _ = _exchange(comp, g, err1, want_stats=False)
        np.testing.assert_allclose(
            np.asarray(used + used2 + err2), np.asarray(g + g),
            rtol=0, atol=1e-5,
        )

    def test_quant_stats_shape_and_underflow(self):
        fp, tree = self._codec()
        comp = GradCompressor(fp, LowPrecisionPolicy(comms_dtype="int8"))
        g = jax.jit(fp.flatten)(tree)
        # crush one segment far below its neighbour's scale: with PER-
        # SEGMENT scales nothing underflows; the stats matrix proves it
        _, _, stats = _exchange(comp, g, None, want_stats=True)
        stats = np.asarray(stats)
        assert stats.shape == (len(fp.sizes) + 1, 3)
        assert (stats[:, 1] == 0).all()  # nothing saturates: scales are amax

    def test_state_precision_round_trip(self):
        fp, tree = self._codec()
        pol = LowPrecisionPolicy(master_dtype="bfloat16",
                                 slot_dtype="bfloat16")
        sp = StatePrecision(fp, pol)
        vec = jax.jit(fp.flatten)(tree)
        stored = sp.encode_master(vec)
        assert stored.dtype == jnp.bfloat16
        back = np.asarray(sp.decode_master(stored))
        assert back.dtype == np.float32
        # bf16: 8 mantissa bits, round to nearest
        np.testing.assert_allclose(back, np.asarray(vec), rtol=8e-3,
                                   atol=1e-6)
        slots = {"m": vec, "v": vec * 0.5}
        enc = sp.encode_slots(slots)
        assert all(v.dtype == jnp.bfloat16 for v in enc.values())
        dec = sp.decode_slots(enc)
        assert all(v.dtype == jnp.float32 for v in dec.values())
        np.testing.assert_allclose(
            np.asarray(dec["m"]), np.asarray(vec), rtol=8e-3, atol=1e-6
        )

    def test_policy_validation(self):
        with pytest.raises(ValueError, match="master_dtype"):
            LowPrecisionPolicy(master_dtype="int8")
        with pytest.raises(ValueError, match="slot_dtype"):
            LowPrecisionPolicy(slot_dtype="float8_e4m3")
        assert LowPrecisionPolicy().active is False
        assert LowPrecisionPolicy(comms_dtype="int8",
                                  error_feedback=False).error_feedback is False
        # error feedback is a comms property: alone it arms nothing
        assert LowPrecisionPolicy(error_feedback=True).active is False


# --------------------------------------------------------------------------
# run level: trajectory tolerance + error feedback strictly helps
# --------------------------------------------------------------------------

def _fit_losses(comms=None, ef=True, master=None, slot=None, seed=11,
                epochs=2, lr=5e-2, n=64, batch=16):
    RandomGenerator.set_seed(seed)
    x, y = _problem(n=n, seed=3)
    tel = Telemetry()
    opt = DistriOptimizer(
        _model(), DataSet.distributed(DataSet.array(x, y, batch_size=batch), 8),
        nn.ClassNLLCriterion(), parameter_sync="sharded",
        comms_dtype=comms, error_feedback=ef,
        master_dtype=master, slot_dtype=slot,
    )
    opt.set_optim_method(SGD(learningrate=lr, momentum=0.9))
    opt.set_end_when(Trigger.max_epoch(epochs))
    opt.set_telemetry(tel)
    opt.optimize()
    losses = [r["loss"] for r in tel.ring.steps()]
    return np.asarray(losses, np.float64), opt


class TestTrajectoryTolerance:
    def test_bf16_comms_within_bound_of_f32(self):
        ref, _ = _fit_losses()
        got, _ = _fit_losses(comms="bfloat16")
        assert np.isfinite(got).all()
        assert np.max(np.abs(got - ref)) < 0.05, np.max(np.abs(got - ref))
        assert got[-1] < got[0]  # it actually trains

    def test_fp8_comms_within_bound_of_f32(self):
        ref, _ = _fit_losses()
        got, _ = _fit_losses(comms="float8_e4m3")
        assert np.isfinite(got).all()
        assert np.max(np.abs(got - ref)) < 0.15, np.max(np.abs(got - ref))
        assert got[-1] < got[0]

    def test_error_feedback_on_strictly_closer_than_off(self):
        """The acceptance lock: int8 is the coarsest wire format, and the
        carried residual must measurably pull the trajectory back toward the
        f32 baseline — EF ON strictly closer than EF OFF, same test, same
        seeds."""
        ref, _ = _fit_losses(epochs=4)
        on, _ = _fit_losses(comms="int8", ef=True, epochs=4)
        off, _ = _fit_losses(comms="int8", ef=False, epochs=4)
        dev_on = float(np.mean(np.abs(on - ref)))
        dev_off = float(np.mean(np.abs(off - ref)))
        assert np.isfinite(on).all() and np.isfinite(off).all()
        assert dev_on < dev_off, (dev_on, dev_off)

    def test_bf16_slots_with_f32_master(self):
        ref, _ = _fit_losses(lr=1e-2)
        got, opt = _fit_losses(slot="bfloat16", lr=1e-2)
        assert np.isfinite(got).all()
        assert np.max(np.abs(got - ref)) < 0.05
        assert _finite(opt.model.get_parameters())


# --------------------------------------------------------------------------
# run level: default path bit-identity + hot-path invariants
# --------------------------------------------------------------------------

class TestDefaultPathUnchanged:
    def test_policy_off_is_bit_identical_to_default_ctor(self):
        ref, ropt = _fit_losses()
        got, gopt = _fit_losses(comms=None, ef=True, master=None, slot=None)
        np.testing.assert_array_equal(ref, got)
        for a, b in zip(_leaves(ropt.model.get_parameters()),
                        _leaves(gopt.model.get_parameters())):
            np.testing.assert_array_equal(a, b)

    def test_default_flat_program_has_no_quant_artifacts(self):
        _, opt = _fit_losses()
        txt = _lower_sharded(opt).as_text()
        assert "f8E" not in txt and "xi8>" not in txt
        assert "all_to_all" not in txt

    def test_ragged_fit_with_compression_is_one_compile_and_schema_valid(self):
        """Acceptance: ragged 2-epoch fit with compression + error feedback
        = exactly 1 compile, health/telemetry schema-valid, quant telemetry
        present, run_start self-describing."""
        RandomGenerator.set_seed(19)
        # 60 % 16 = 12: a ragged epoch tail that does not divide over the 8
        # devices, so the distributed dataset drops it before the step
        x, y = _problem(n=60)
        tel = Telemetry()
        opt = DistriOptimizer(
            _model(), DataSet.distributed(DataSet.array(x, y, batch_size=16), 8),
            nn.ClassNLLCriterion(), parameter_sync="sharded",
            comms_dtype="int8", error_feedback=True, slot_dtype="bfloat16",
        )
        opt.set_optim_method(Adam(learningrate=1e-2))
        opt.set_end_when(Trigger.max_epoch(2))
        opt.set_telemetry(tel)
        opt.set_health(HealthConfig(every_n_steps=1))
        opt.optimize()
        assert tel.compile_count == 1
        assert opt._jit_step._cache_size() == 1
        recs = tel.ring.records
        for r in recs:
            obs_report.validate_record(r)
        healths = [r for r in recs if r["type"] == "health"]
        assert healths
        for h in healths:
            q = h.get("quant")
            assert q is not None
            assert {"scale_amax", "saturated", "underflow"} <= set(q)
            assert q["saturated"] == 0  # scales are exact amax
            assert "layers" in q  # per-segment rows ride per_layer mode
        starts = [r for r in recs
                  if r["type"] == "meta" and r.get("event") == "run_start"]
        assert starts and starts[0]["low_precision"] == {
            "comms_dtype": "int8", "error_feedback": True,
            "master_dtype": None, "slot_dtype": "bfloat16",
        }

    def test_retry_reuses_cached_step_with_compression(self, tmp_path):
        class _FailOnce(AbstractDataSet):
            def __init__(self, base, fail_at):
                self.base, self.fail_at = base, fail_at
                self.served, self.failed = 0, False

            def size(self):
                return self.base.size()

            def shuffle(self, epoch=None):
                self.base.shuffle(epoch)

            def data(self, train):
                for b in self.base.data(train):
                    if (train and not self.failed
                            and self.served == self.fail_at):
                        self.failed = True
                        raise RuntimeError("injected executor failure")
                    if train:
                        self.served += 1
                    yield b

        RandomGenerator.set_seed(21)
        x, y = _problem()
        ds = _FailOnce(DataSet.array(x, y, batch_size=8), fail_at=9)
        opt = DistriOptimizer(
            _model(), DataSet.distributed(ds, 8), nn.ClassNLLCriterion(),
            parameter_sync="sharded", comms_dtype="int8", error_feedback=True,
        )
        opt.set_optim_method(SGD(learningrate=0.1, momentum=0.9))
        opt.set_end_when(Trigger.max_iteration(16))
        opt.set_checkpoint(str(tmp_path), Trigger.several_iteration(2))
        opt.set_retry_times(2)
        opt.optimize()
        assert ds.failed
        assert opt._jit_step._cache_size() == 1  # the compiled step survived

    @pytest.mark.parametrize("kw", [
        dict(comms_dtype="int8", error_feedback=True),
        dict(master_dtype="bfloat16", slot_dtype="bfloat16"),
    ], ids=["comms_int8", "bf16_state"])
    def test_one_device_mesh_carries_the_policy(self, kw):
        """The one-chip home of the policies: the ZeRO-1 step over a
        one-device mesh trains with them at one compile, its master and
        slots carried in the storage dtype."""
        from bigdl_tpu.utils.engine import Engine

        Engine.init(devices=jax.devices()[:1])
        RandomGenerator.set_seed(17)
        x, y = _problem(n=32)
        tel = Telemetry()
        opt = DistriOptimizer(
            _model(), DataSet.distributed(DataSet.array(x, y, batch_size=8), 1),
            nn.ClassNLLCriterion(), parameter_sync="sharded", **kw,
        )
        opt.set_optim_method(Adam(learningrate=1e-2))
        opt.set_end_when(Trigger.max_epoch(2))
        opt.set_telemetry(tel)
        opt.optimize()
        assert opt._perf_device_count() == 1
        assert tel.compile_count == 1
        losses = [r["loss"] for r in tel.ring.steps()]
        assert len(losses) == 8 and np.isfinite(losses).all()
        assert _finite(opt.model.get_parameters())
        master, slots = opt._step_export_info[1][0], opt._step_export_info[1][2]
        want = jnp.bfloat16 if "master_dtype" in kw else jnp.float32
        assert master.dtype == want
        assert all(v.dtype == want for v in jax.tree_util.tree_leaves(slots)
                   if v.ndim == 1)

    def test_non_flat_local_refuses_policy(self):
        x, y = _problem(n=16)
        opt = LocalOptimizer(
            _model(), DataSet.array(x, y, batch_size=8),
            nn.ClassNLLCriterion(), comms_dtype="int8",
        )
        with pytest.raises(ValueError, match="parameter_sync='sharded'"):
            opt.optimize()


# --------------------------------------------------------------------------
# checkpoints: quantized ↔ unquantized round trips (tree layout / f32)
# --------------------------------------------------------------------------

class TestQuantizedCheckpointRoundTrip:
    def _make_opt(self, quantized: bool):
        x, y = _problem()
        kw = {}
        if quantized:
            kw = dict(comms_dtype="int8", error_feedback=True,
                      slot_dtype="bfloat16")
        opt = DistriOptimizer(
            _model(), DataSet.distributed(DataSet.array(x, y, batch_size=8), 8),
            nn.ClassNLLCriterion(), parameter_sync="sharded", **kw,
        )
        opt.set_optim_method(Adam(learningrate=1e-2))
        opt.set_end_when(Trigger.max_epoch(2))
        return opt

    @pytest.mark.parametrize("first,second", [
        (True, False), (False, True),
    ], ids=["quantized_to_f32", "f32_to_quantized"])
    def test_round_trip(self, tmp_path, first, second):
        """The compatibility contract: checkpoints are written in tree
        layout / f32 whatever the in-flight storage precision, so a run
        interrupted under one policy resumes under the other — same
        manifests, same keys, f32 arrays, finite continuation."""
        from bigdl_tpu.utils import serialization as ser

        RandomGenerator.set_seed(24)
        ckpt = str(tmp_path / "ckpt")
        opt1 = self._make_opt(first)
        opt1.set_end_when(Trigger.max_iteration(8))
        opt1.set_checkpoint(ckpt, Trigger.several_iteration(2))
        opt1.optimize()
        step = ser.latest_checkpoint_step(ckpt)
        assert step is not None
        manifest = ser.checkpoint_manifest(ckpt, step)
        assert manifest["slot_layout"] == "tree"
        params, slots, _host, _ms = ser.load_checkpoint(
            ckpt, params_like=opt1.model.get_parameters()
        )
        for arr in jax.tree_util.tree_leaves((params, slots)):
            assert np.asarray(arr).dtype == np.float32  # f32 on disk, always

        RandomGenerator.set_seed(24)
        opt2 = self._make_opt(second)
        opt2.resume(ckpt)
        model = opt2.optimize()
        assert _finite(model.get_parameters())
        assert opt2.optim_method.state["neval"] > 8


# --------------------------------------------------------------------------
# program level: the collective operand-bytes lock (ZeRO-1 sharded step)
# --------------------------------------------------------------------------

def _deep_model(d=6, classes=3, hidden=32, depth=4):
    layers = [nn.Linear(d, hidden), nn.Tanh()]
    for _ in range(depth):
        layers += [nn.Linear(hidden, hidden), nn.Tanh()]
    layers += [nn.Linear(hidden, classes), nn.LogSoftMax()]
    return nn.Sequential(*layers)


def _sharded_fit(**kw):
    RandomGenerator.set_seed(5)
    x, y = _problem(n=64)
    ds = DataSet.distributed(DataSet.array(x, y, batch_size=16), 8)
    opt = DistriOptimizer(_deep_model(), ds, nn.ClassNLLCriterion(),
                          parameter_sync="sharded", **kw)
    opt.set_optim_method(Adam(learningrate=1e-2))
    opt.set_end_when(Trigger.max_epoch(2))
    opt.set_telemetry(Telemetry())
    opt.optimize()
    return opt


def _lower_sharded(opt):
    (fp,) = opt._flat_fp.values()
    method = opt.optim_method
    pol = opt._precision
    mdtype = jnp.float32
    if pol is not None and pol.master_dtype is not None:
        mdtype = pol.master_dtype
    p0 = jax.ShapeDtypeStruct((fp.padded_total,), mdtype)
    slots = jax.eval_shape(
        method.init_slots, jax.ShapeDtypeStruct((fp.padded_total,),
                                                jnp.float32)
    )
    if pol is not None and pol.slot_dtype is not None:
        slots = {k: jax.ShapeDtypeStruct(v.shape, pol.slot_dtype)
                 for k, v in slots.items()}
    args = [
        p0,
        jax.eval_shape(lambda: _tm(jnp.asarray, opt.model.get_state())),
        slots,
    ]
    if pol is not None and pol.comms_dtype is not None and pol.error_feedback:
        args.append(jax.ShapeDtypeStruct((8, fp.padded_total), jnp.float32))
    args += [
        jax.ShapeDtypeStruct((16, 6), jnp.float32),
        jax.ShapeDtypeStruct((16,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.float32),  # nvalid
        jax.ShapeDtypeStruct((), jnp.float32),  # lr
        jax.ShapeDtypeStruct((), jnp.int32),    # step
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    ]
    return opt._jit_step.lower(*args)


class TestShardedCollectiveBytes:
    """The acceptance lock: gradient-exchange collective operand bytes
    (reduce_scatter / all_to_all — what each device puts on the wire to
    aggregate gradients) ≥2× smaller under bf16 and ≥3.5× under fp8/int8,
    with the default program untouched. Everything here lowers the REAL
    cached SPMD step the fits above dispatched."""

    def test_bytes_lock_and_one_compile(self):
        base_opt = _sharded_fit()
        assert base_opt.telemetry.compile_count == 1
        base = collective_bytes(_lower_sharded(base_opt))
        assert base["grad_exchange_bytes"] > 0
        assert base["by_op"].get("all_to_all", 0) == 0  # pure reduce-scatter

        bf_opt = _sharded_fit(comms_dtype="bfloat16")
        assert bf_opt.telemetry.compile_count == 1
        bf = collective_bytes(_lower_sharded(bf_opt))
        assert base["grad_exchange_bytes"] / bf["grad_exchange_bytes"] >= 2.0

        for dtype in ("int8", "float8_e5m2"):
            q_opt = _sharded_fit(comms_dtype=dtype, error_feedback=True)
            assert q_opt.telemetry.compile_count == 1
            assert _finite(q_opt.model.get_parameters())
            q = collective_bytes(_lower_sharded(q_opt))
            ratio = base["grad_exchange_bytes"] / q["grad_exchange_bytes"]
            assert ratio >= 3.5, (dtype, ratio, q["by_op"])
            # the scale pmax is a tiny all_reduce, never a second full pass
            assert q["all_reduce_bytes"] < 1024, q["by_op"]
            # the weight all-gather is untouched by a comms-only policy
            assert q["all_gather_bytes"] == base["all_gather_bytes"]

    def test_bf16_master_also_halves_the_weight_all_gather(self):
        base = collective_bytes(_lower_sharded(_sharded_fit()))
        low = collective_bytes(_lower_sharded(_sharded_fit(
            comms_dtype="float8_e5m2", master_dtype="bfloat16",
            slot_dtype="bfloat16",
        )))
        assert low["all_gather_bytes"] * 2 == base["all_gather_bytes"]

    def test_default_sharded_program_is_unchanged(self):
        """Byte-for-byte: an optimizer built with the policy kwargs left at
        their defaults lowers the IDENTICAL program text as one that never
        mentions them — and it contains no quantization artifacts."""
        txt_a = _lower_sharded(_sharded_fit()).as_text()
        txt_b = _lower_sharded(_sharded_fit(
            comms_dtype=None, error_feedback=True,
            master_dtype=None, slot_dtype=None,
        )).as_text()
        assert txt_a == txt_b
        assert "f8E" not in txt_a and "all_to_all" not in txt_a

    def test_replicated_refuses_policy(self):
        x, y = _problem(n=64)
        ds = DataSet.distributed(DataSet.array(x, y, batch_size=16), 8)
        opt = DistriOptimizer(_model(), ds, nn.ClassNLLCriterion(),
                              parameter_sync="replicated",
                              comms_dtype="bfloat16")
        with pytest.raises(ValueError, match="parameter_sync='sharded'"):
            opt.optimize()


def _pipeline_with_master(ds, **kw):
    from bigdl_tpu.parallel.pipeline_optimizer import PipelineOptimizer

    return PipelineOptimizer(_model(), ds, nn.ClassNLLCriterion(), **kw)


@pytest.mark.parametrize("make,error", [
    (lambda ds, **kw: LocalOptimizer(_model(), ds, nn.ClassNLLCriterion(),
                                     **kw), ValueError),
    (lambda ds, **kw: DistriOptimizer(_model(), DataSet.distributed(ds, 8),
                                      nn.ClassNLLCriterion(), **kw),
     ValueError),
    # the stacked-parameter optimizers take no state policy at all
    (_pipeline_with_master, TypeError),
], ids=["local", "distri", "pipeline"])
def test_float8_master_refused_at_construction(make, error):
    """No step carries a float8 master: every optimizer refuses one when it
    is built, before any data is read or program traced."""
    x, y = _problem(n=16)
    with pytest.raises(error, match="master_dtype"):
        make(DataSet.array(x, y, batch_size=16), master_dtype="float8_e4m3")


# --------------------------------------------------------------------------
# satellite: GSPMD/hybrid health localizes the poisoned mesh shard
# --------------------------------------------------------------------------

class _PoisonShard(AbstractDataSet):
    """Poisons the rows belonging to ONE data shard of one batch of epoch 1
    (a retry replaying that position hits it again — the fails-twice poison
    classification — but later epochs are clean)."""

    def __init__(self, base, n_shards, shard, at_batch):
        self.base, self.n_shards = base, n_shards
        self.shard, self.at_batch = shard, at_batch
        self._epoch = 1

    def size(self):
        return self.base.size()

    def shuffle(self, epoch=None):
        if epoch is not None:
            self._epoch = int(epoch)
        self.base.shuffle(epoch)

    def data(self, train):
        for i, b in enumerate(self.base.data(train)):
            if train and self._epoch == 1 and i == self.at_batch:
                xb = np.asarray(b.get_input()).copy()
                rows = xb.shape[0] // self.n_shards
                xb[self.shard * rows:(self.shard + 1) * rows] = np.nan
                b = MiniBatch(xb, b.get_target())
            yield b


class TestHybridMeshShardHealth:
    def _fit(self, poison_shard=None, policy=False, tmp_path=None):
        from bigdl_tpu.parallel.hybrid import (
            HybridParallelOptimizer, make_mesh,
        )

        RandomGenerator.set_seed(7)
        x, y = _problem(n=64)
        ds = DataSet.array(x, y, batch_size=32)
        if poison_shard is not None:
            ds = _PoisonShard(ds, n_shards=4, shard=poison_shard, at_batch=1)
        mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
        tel = Telemetry()
        opt = HybridParallelOptimizer(
            _model(), ds, nn.ClassNLLCriterion(), mesh=mesh
        )
        opt.set_optim_method(SGD(learningrate=0.1, momentum=0.9))
        opt.set_end_when(Trigger.max_iteration(4))
        opt.set_telemetry(tel)
        opt.set_health(HealthConfig(every_n_steps=1))
        if policy:
            opt.set_checkpoint(str(tmp_path / "ckpt"),
                               Trigger.several_iteration(1))
            opt.set_failure_policy(FailurePolicy(backoff_base_s=0.0))
        opt.optimize()
        return opt, tel

    def test_health_records_carry_per_shard_rows(self):
        opt, tel = self._fit()
        healths = [r for r in tel.ring.records if r["type"] == "health"]
        assert healths
        for h in healths:
            obs_report.validate_record(h)
            shards = h.get("shards")
            assert shards is not None
            assert set(shards) == {f"data[{i}]" for i in range(4)}
            assert all(v["nonfinite_inputs"] == 0 for v in shards.values())
        assert tel.compile_count == 1  # per-shard stats cost no retrace

    def test_poisoned_shard_is_localized(self):
        opt, tel = self._fit(poison_shard=2)
        healths = [r for r in tel.ring.records if r["type"] == "health"]
        hit = [h for h in healths
               if h["shards"]["data[2]"]["nonfinite_inputs"] > 0]
        assert hit, "poisoned shard never surfaced in the health stream"
        for h in hit:
            clean = [k for k, v in h["shards"].items()
                     if v["nonfinite_inputs"] > 0]
            assert clean == ["data[2]"]  # ONLY the poisoned mesh coordinate

    def test_rollback_record_names_the_mesh_shard(self, tmp_path):
        """End to end: the NaN input diverges the loss, the divergence guard
        rolls back, and the rollback record blames data[2] — the mesh-axis
        localization the ROADMAP satellite asked for."""
        opt, tel = self._fit(poison_shard=2, policy=True, tmp_path=tmp_path)
        rollbacks = [r for r in tel.ring.records if r["type"] == "rollback"]
        assert rollbacks, "divergence guard never fired"
        for r in rollbacks:
            obs_report.validate_record(r)
            assert r["shard"] == "data[2]"
        assert _finite(opt.model.get_parameters())

    def test_attribute_shard_unit(self):
        from bigdl_tpu.obs.health import HealthMonitor

        hm = HealthMonitor()
        hm.bind_mesh_axis("data", 4)
        snap = {"shards": np.array(
            [[0, 0], [0, 0], [3, 0], [1, 0]], np.float32
        )}
        assert hm.attribute_shard(snap) == "data[2]"
        clean = {"shards": np.zeros((4, 2), np.float32)}
        assert hm.attribute_shard(clean) is None
        assert hm.attribute_shard({}) is None
