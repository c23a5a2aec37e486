"""Benchmark driver: flagship-model training throughput on the chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Analog of the reference's synthetic-batch perf drivers
(``$DL/models/utils/DistriOptimizerPerf.scala`` / ``LocalOptimizerPerf.scala``),
which produced BigDL's published throughput numbers: jitted train step over
synthetic data, steady-state images/sec after a warmup.

One process, no retries: the measurement runs right here, and anything that
stops it — the device cannot be reached, a compile fails, a measurement
raises — propagates, so the exit code is non-zero and the traceback is the
artifact (the flight recorder additionally leaves a postmortem bundle under
the run dir). A run that measured nothing never exits 0.

``vs_baseline`` is null: BASELINE.json.published is empty (reference mount
unavailable both rounds — see BASELINE.md). No fabricated divisor.
"""

from __future__ import annotations

import json
import os
from functools import partial
import time

BATCH = int(os.environ.get("BENCH_BATCH", "128"))  # b128 measured +20% over b64 on v5e
WARMUP_STEPS = 3
MEASURE_STEPS = 20
MEASURE_WINDOWS = 5  # report the median window


def _peak_flops(device_kind: str):
    """Per-chip bf16 peak — resolved through utils/compat.device_peaks, the
    SAME table the live obs/perf.py MFU accounting divides by, so the bench
    headline and a run's telemetry perf records can never disagree on the
    denominator. ``None`` only for the CPU backend (which has no peak — the
    benchmark PR removes the CPU paths that can reach this); an accelerator
    the table does not know raises there."""
    from bigdl_tpu.utils.compat import device_peaks

    peaks = device_peaks(device_kind)
    return peaks.flops if peaks is not None else None


def _mfu_estimate(step_flops, step_wall_s, device_kind):
    """The live cost model's MFU figure (obs/perf.py) over the measured
    steady-state step wall — the headline's `mfu_estimate` field, computed
    by the same code path that stamps every telemetry step record."""
    from bigdl_tpu.obs.perf import mfu as _mfu

    return _mfu(step_flops, step_wall_s, _peak_flops(device_kind))


def _step_flops(compiled):
    """XLA's own flop count for a compiled step (None when it reports 0)."""
    return float((compiled.cost_analysis() or {}).get("flops", 0.0)) or None


def _measure_files() -> dict:
    """File-fed variant (BENCH_MODE=files): the same jitted train step, but
    every batch comes off DISK through the sharded reader + fused host
    normalize + prefetch thread — measures the full input pipeline against
    the synthetic number (reference: SeqFileFolder-fed DistriOptimizerPerf)."""
    import queue
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import native, nn
    from bigdl_tpu.dataset import Sample, ShardedRecordDataSet, write_record_shards
    from bigdl_tpu.models import flagship_model
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(1)
    dtype = os.environ.get("BENCH_COMPUTE_DTYPE", "bfloat16")
    Engine.set_compute_dtype(dtype)
    act_dtype = os.environ.get("BENCH_ACT_DTYPE", "bfloat16")
    if act_dtype != "float32":
        Engine.set_activation_dtype(act_dtype)  # same policy as the headline
    model, x, labels, name = flagship_model(batch=BATCH)
    criterion = nn.ClassNLLCriterion()
    method = SGD(learningrate=0.1, momentum=0.9)
    params, state = model.init(sample_input=x)
    slots = method.init_slots(params)

    mean_dev = jnp.float32([127.0, 127.0, 127.0])
    std_dev = jnp.float32([63.0, 63.0, 63.0])

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, state, slots, x_u8, t, rng):
        # normalize + HWC->CHW ON DEVICE: the wire format stays uint8 (4x
        # less host->device traffic than f32, and the cast/transpose fuse
        # into the first conv)
        x = (x_u8.astype(jnp.float32) - mean_dev) / std_dev
        x = x.transpose(0, 3, 1, 2)

        def loss_fn(p):
            y, s = model.apply(p, state, x, training=True, rng=rng)
            return criterion._apply(y, t), s

        (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, slots = method.update(
            grads, params, slots, jnp.asarray(0.1), jnp.asarray(1)
        )
        return params, new_state, slots, loss

    h, w = x.shape[2], x.shape[3]
    n_images = BATCH * (WARMUP_STEPS + 2 * MEASURE_STEPS)
    shard_dir = os.path.join(
        os.environ.get("TMPDIR", "/tmp"), f"bigdl_bench_shards_{h}x{w}"
    )
    if not os.path.isdir(shard_dir) or not os.listdir(shard_dir):
        rng_np = np.random.default_rng(0)
        write_record_shards(
            (
                (rng_np.integers(0, 255, (h, w, 3), np.uint8).tobytes(), i % 1000)
                for i in range(n_images)
            ),
            shard_dir,
            records_per_shard=BATCH * 4,
        )

    def decode(payload, label):
        img = np.frombuffer(payload, np.uint8).reshape(h, w, 3)
        return Sample(img, np.int64(label))

    ds = ShardedRecordDataSet(
        sorted(
            os.path.join(shard_dir, f) for f in os.listdir(shard_dir)
        ),
        decode,
        batch_size=BATCH,
        n_workers=int(os.environ.get("BENCH_DECODE_WORKERS", "6")),
    )
    # multi-worker host pipeline (docs/performance.md input-pipeline
    # section): BENCH_PIPELINE_WORKERS sets the DataPipeline transform/
    # assembly pool — workers=1 vs N on the same round is the CPU-side
    # starvation A/B the next TPU round measures on the flagship step
    from bigdl_tpu.dataset import DataPipeline

    pipeline_workers = int(os.environ.get("BENCH_PIPELINE_WORKERS", "4"))
    pipe = DataPipeline(ds, num_workers=pipeline_workers, depth=4,
                        batch_size=BATCH)
    input_waits = []  # per-batch wait for the pipeline (steady-state slice)

    def batches():
        """Endless file-fed device batches through a depth-2 prefetch thread."""
        q: "queue.Queue" = queue.Queue(maxsize=2)

        def worker():
            epoch = 0
            while True:
                it = pipe.data(train=True)
                while True:
                    t_wait = time.perf_counter()
                    b = next(it, None)
                    if b is None:
                        break
                    input_waits.append(time.perf_counter() - t_wait)
                    xb = np.ascontiguousarray(b.get_input())  # uint8 (B,H,W,C)
                    tb = np.asarray(b.get_target()).reshape(-1)
                    q.put(jax.device_put((xb, tb)))
                epoch += 1
                pipe.shuffle(epoch)

        threading.Thread(target=worker, daemon=True).start()
        while True:
            yield q.get()

    # host-pipeline-only capacity: how fast can disk->decode->transform->batch
    # go with no device in the loop (separates pipeline speed from the h2d
    # link)
    t0 = time.perf_counter()
    host_images = sum(b.size() for b in pipe.data(train=True))
    host_rate = round(host_images / (time.perf_counter() - t0), 2)
    pipe.shuffle(123)

    it = batches()
    rng = jax.random.PRNGKey(0)
    for _ in range(WARMUP_STEPS):
        xb, tb = next(it)
        params, state, slots, loss = train_step(params, state, slots, xb, tb, rng)
    float(loss)

    windows = []
    for _ in range(MEASURE_WINDOWS):
        t0 = time.perf_counter()
        for _ in range(MEASURE_STEPS):
            xb, tb = next(it)
            params, state, slots, loss = train_step(
                params, state, slots, xb, tb, rng
            )
        float(loss)
        windows.append(time.perf_counter() - t0)
    # snapshot NOW: the prefetch worker keeps pulling (and appending) after
    # the measured window ends; the steady-state slice drops the warmup-era
    # pulls (pipeline spin-up — prefetch depth makes the boundary approximate)
    steady = sorted(list(input_waits)[WARMUP_STEPS:]) or [0.0]
    windows.sort()
    elapsed = windows[len(windows) // 2]
    device = jax.devices()[0]
    return {
        "metric": f"{name} train images/sec/chip FILE-FED (batch {BATCH}, "
                  f"{dtype}, pipeline_workers={pipeline_workers})",
        "value": round(MEASURE_STEPS * BATCH / elapsed, 2),
        "unit": "images/sec/chip",
        "vs_baseline": None,
        "step_ms": round(elapsed / MEASURE_STEPS * 1e3, 2),
        "window_step_ms": [round(t / MEASURE_STEPS * 1e3, 2) for t in windows],
        "host_pipeline_images_per_sec": host_rate,
        # input-pipeline surface (BENCH_PIPELINE_WORKERS A/B on the next TPU
        # round): per-batch host wait for the multi-worker pipeline
        "pipeline_workers": pipeline_workers,
        "input_wait_ms_p50": round(steady[len(steady) // 2] * 1e3, 3),
        "input_wait_ms_mean": round(
            sum(steady) / len(steady) * 1e3, 3
        ),
        "input_wait_ms_max": round(steady[-1] * 1e3, 3),
        "note": "uint8 wire + on-device normalize",
        "device_kind": device.device_kind,
        "platform": device.platform,
    }


def _measure_flash() -> dict:
    """Flash-attention kernel microbench (BENCH_MODE=flash): Pallas fwd+bwd
    vs the dense XLA path across sequence lengths, causal bf16 — the
    on-TPU evidence for the custom-kernel row (SURVEY.md §2.6)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops import flash_attention
    from bigdl_tpu.ops.flash_attention import _dense_reference

    def med(fn, *args, reps=5, inner=10):
        out = fn(*args)
        float(jnp.sum(out[0].astype(jnp.float32)))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(inner):
                out = fn(*args)
            float(jnp.sum(out[0].astype(jnp.float32)))
            ts.append((time.perf_counter() - t0) / inner * 1e3)
        ts.sort()
        return ts[len(ts) // 2]

    rng = np.random.default_rng(0)
    rows = []
    for t in (2048, 4096, 8192, 16384):
        n, h, d = (2, 8, 128) if t <= 4096 else (1, 8, 128)
        q, k, v = (
            jnp.asarray(rng.standard_normal((n, h, t, d)), jnp.bfloat16)
            for _ in range(3)
        )
        fl = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, True).astype(jnp.float32)
            ), argnums=(0, 1, 2),
        ))
        de = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                _dense_reference(q, k, v, True, None).astype(jnp.float32)
            ), argnums=(0, 1, 2),
        ))
        flash_ms = med(fl, q, k, v)
        try:
            dense_ms = med(de, q, k, v)
        except Exception:
            dense_ms = None  # dense OOMs at long T; flash is the only path
        rows.append({
            "seq_len": t, "flash_ms": round(flash_ms, 2),
            "dense_ms": round(dense_ms, 2) if dense_ms else None,
            "speedup": round(dense_ms / flash_ms, 2) if dense_ms else None,
        })
    best = max((r for r in rows if r["speedup"]), key=lambda r: r["speedup"],
               default=rows[-1])
    device = jax.devices()[0]
    return {
        "metric": "flash-attention fwd+bwd speedup vs dense XLA "
                  f"(causal bf16, T={best['seq_len']})",
        "value": best.get("speedup"),
        "unit": "x",
        "vs_baseline": None,
        "rows": rows,
        "device_kind": device.device_kind,
        "platform": device.platform,
    }


def _parity_config(name: str):
    """Model + synthetic batch for one of the five BASELINE parity configs.

    Returns (model, x, labels, batch) — every model ends in LogSoftMax, so
    `_measure_one_config` pairs them all with ClassNLL (reference recipes).
    """
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.models import (
        BiLSTMClassifier, Inception_v1, LeNet5, VggForCifar10, WideAndDeep,
    )

    rng = np.random.default_rng(0)
    if name == "lenet":
        batch = int(os.environ.get("BENCH_CFG_BATCH", "512"))
        x = rng.standard_normal((batch, 784)).astype(np.float32)
        t = rng.integers(0, 10, batch)
        return LeNet5(10), x, t, batch
    if name == "vgg":
        batch = int(os.environ.get("BENCH_CFG_BATCH", "128"))
        x = rng.standard_normal((batch, 3, 32, 32)).astype(np.float32)
        t = rng.integers(0, 10, batch)
        return VggForCifar10(10), x, t, batch
    if name == "inception":
        batch = int(os.environ.get("BENCH_CFG_BATCH", "128"))
        x = rng.standard_normal((batch, 3, 224, 224)).astype(np.float32)
        t = rng.integers(0, 1000, batch)
        return Inception_v1(1000), x, t, batch
    if name == "bilstm":
        batch = int(os.environ.get("BENCH_CFG_BATCH", "128"))
        seq = int(os.environ.get("BENCH_SEQ_LEN", "200"))
        hidden = int(os.environ.get("BENCH_LSTM_HIDDEN", "128"))  # scan probe knob
        x = rng.integers(1, 20000, (batch, seq)).astype(np.int32)
        t = rng.integers(0, 20, batch)
        return BiLSTMClassifier(vocab_size=20001, hidden_size=hidden), x, t, batch
    if name == "widedeep":
        from bigdl_tpu.dataset.criteo import load_criteo

        batch = int(os.environ.get("BENCH_CFG_BATCH", "2048"))
        table, labels = load_criteo(None, n=batch)
        return WideAndDeep(class_num=2), table, labels, batch
    raise ValueError(f"unknown parity config {name!r}")


def _measure_one_config(name: str) -> dict:
    """Jitted-train-step throughput for one parity config (same protocol as
    the flagship `_measure`: warmup + median of timed windows, scalar-pull
    sync)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(1)
    dtype = os.environ.get("BENCH_COMPUTE_DTYPE", "bfloat16")
    Engine.set_compute_dtype(dtype)
    act_dtype = os.environ.get("BENCH_ACT_DTYPE", "bfloat16")
    if act_dtype != "float32":
        Engine.set_activation_dtype(act_dtype)

    model, x, t, batch = _parity_config(name)
    criterion = nn.ClassNLLCriterion()
    method = SGD(learningrate=0.01, momentum=0.9)
    params, state = model.init(sample_input=x)
    slots = method.init_slots(params)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, state, slots, x, t, rng):
        def loss_fn(p):
            y, s = model.apply(p, state, x, training=True, rng=rng)
            return criterion._apply(y, t), s

        (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, slots = method.update(
            grads, params, slots, jnp.asarray(0.01), jnp.asarray(1)
        )
        return params, new_state, slots, loss

    xs = jax.tree_util.tree_map(jnp.asarray, x)
    ts = jnp.asarray(t)
    rng = jax.random.PRNGKey(0)
    from bigdl_tpu.utils import compat as _compat

    cache_before = _compat.compilation_cache_entries()
    t0 = time.perf_counter()
    compiled = train_step.lower(params, state, slots, xs, ts, rng).compile()
    compile_seconds = round(time.perf_counter() - t0, 2)
    cache_hit = _compat.compilation_cache_hit(
        cache_before, _compat.compilation_cache_entries()
    )
    step_flops = _step_flops(compiled)
    for _ in range(WARMUP_STEPS):
        params, state, slots, loss = train_step(params, state, slots, xs, ts, rng)
    float(loss)
    compile_s = time.perf_counter() - t0
    windows = []
    for _ in range(MEASURE_WINDOWS):
        t0 = time.perf_counter()
        for _ in range(MEASURE_STEPS):
            params, state, slots, loss = train_step(
                params, state, slots, xs, ts, rng
            )
        float(loss)
        windows.append(time.perf_counter() - t0)
    windows.sort()
    elapsed = windows[len(windows) // 2]
    peak = _peak_flops(jax.devices()[0].device_kind)
    mfu = None
    if step_flops and peak:
        mfu = round(step_flops / (elapsed / MEASURE_STEPS) / peak, 4)
    # what limits each config on this part (VERDICT r3 next #7): tiny-model
    # configs never fill the chip — their step is dispatch/latency-bound —
    # while the convnets run into HBM bandwidth (TRACE_ANALYSIS_r3.md) and
    # the LSTM's scan is MXU-serialization-bound
    bound = {
        "lenet": "latency-bound (sub-ms step; chip mostly idle)",
        "widedeep": "latency/gather-bound (embedding lookups, tiny matmuls)",
        "vgg": "HBM-bandwidth-bound (conv fusions)",
        "inception": "HBM-bandwidth-bound (conv fusions + maxpool grads)",
        "bilstm": "MXU-serialization-bound (lax.scan over T)",
    }.get(name)
    return {
        "config": name,
        "records_per_sec": round(MEASURE_STEPS * batch / elapsed, 2),
        "step_ms": round(elapsed / MEASURE_STEPS * 1e3, 2),
        "batch": batch,
        "step_flops": step_flops,
        "mfu": mfu,
        "mfu_estimate": _mfu_estimate(
            step_flops, elapsed / MEASURE_STEPS,
            jax.devices()[0].device_kind,
        ),
        "bound": bound,
        "compile_seconds": compile_seconds,
        "compile_cache_hit": cache_hit,
        "warmup_incl_compile_s": round(compile_s, 1),
    }


def _measure_configs() -> dict:
    """BENCH_MODE=configs: all five BASELINE parity configs in one run
    (VERDICT r2 next #4). BENCH_CONFIG=<name> limits to one."""
    import math

    import jax

    names = (
        [os.environ["BENCH_CONFIG"]]
        if os.environ.get("BENCH_CONFIG")
        else ["lenet", "vgg", "inception", "bilstm", "widedeep"]
    )
    rows = [_measure_one_config(n) for n in names]
    gmean = math.exp(
        sum(math.log(r["records_per_sec"]) for r in rows) / len(rows)
    )
    device = jax.devices()[0]
    result = {
        "metric": "BASELINE parity configs train records/sec/chip "
                  f"(geomean of {len(rows)}: {','.join(names)})",
        "value": round(gmean, 2),
        "unit": "records/sec/chip",
        "vs_baseline": None,
        "rows": rows,
        "device_kind": device.device_kind,
        "platform": device.platform,
    }
    # committed per-config artifact (VERDICT r3 next #7): throughput,
    # step_ms, step_flops, MFU and boundedness per workload
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    if len(rows) == 5 and os.path.isdir(art_dir):
        with open(os.path.join(art_dir, "CONFIGS_r05.json"), "w") as f:
            json.dump(result, f, indent=1)
    return result


def _measure_int8() -> dict:
    """BENCH_MODE=int8: quantized ResNet-50 INFERENCE throughput vs bf16 on
    the same model (VERDICT r2 next #7) — first on-chip evidence for the
    nn/quantized int8 MXU path (int8 dot_general/conv, int32 accumulation)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import flagship_model
    from bigdl_tpu.nn.quantized import quantize
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(1)
    Engine.set_compute_dtype(os.environ.get("BENCH_COMPUTE_DTYPE", "bfloat16"))
    model, x, _, name = flagship_model(batch=BATCH, stem="conv7")
    params, state = model.init(sample_input=x)
    xs = jnp.asarray(x)

    def timed(fn, *args):
        out = fn(*args)
        float(jnp.sum(out.astype(jnp.float32)))
        windows = []
        for _ in range(MEASURE_WINDOWS):
            t0 = time.perf_counter()
            for _ in range(MEASURE_STEPS):
                out = fn(*args)
            float(jnp.sum(out.astype(jnp.float32)))
            windows.append(time.perf_counter() - t0)
        windows.sort()
        return MEASURE_STEPS * BATCH / windows[len(windows) // 2]

    bf16_fwd = jax.jit(
        lambda p, s, xx: model.apply(p, s, xx, training=False, rng=None)[0]
    )
    bf16_ips = timed(bf16_fwd, params, state, xs)

    qmodel = quantize(model)
    qparams, qstate = qmodel.get_parameters(), qmodel.get_state()
    q_fwd = jax.jit(
        lambda p, s, xx: qmodel.apply(p, s, xx, training=False, rng=None)[0]
    )
    q_ips = timed(q_fwd, qparams, qstate, xs)

    device = jax.devices()[0]
    return {
        "metric": f"{name} INT8 inference images/sec/chip (batch {BATCH})",
        "value": round(q_ips, 2),
        "unit": "images/sec/chip",
        "vs_baseline": None,
        "bf16_images_per_sec": round(bf16_ips, 2),
        "int8_vs_bf16": round(q_ips / bf16_ips, 3),
        "device_kind": device.device_kind,
        "platform": device.platform,
    }


def _measure_lowprec() -> dict:
    """BENCH_MODE=lowprec: the low-precision flat-path campaign entry
    (docs/performance.md). Runs the REAL ZeRO-1 sharded DistriOptimizer fit
    twice — f32 baseline vs the BENCH_COMMS_DTYPE / BENCH_QUANT policy — and
    reports step time plus the lowered program's collective operand bytes
    (the hardware-independent wire-compression proof: the artifact carries
    the policy AND the all-reduce-bytes ratio, so a CPU run still stands
    behind the bytes claim while the TPU round adds the step-time one).

    Knobs: ``BENCH_COMMS_DTYPE`` (bfloat16 | int8 | float8_e4m3 |
    float8_e5m2; default bfloat16), ``BENCH_QUANT`` (JSON, e.g.
    ``{"slot_dtype": "bfloat16", "master_dtype": null,
    "error_feedback": true}``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.obs.profiler import collective_bytes
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
    from bigdl_tpu.utils.random import RandomGenerator

    comms = os.environ.get("BENCH_COMMS_DTYPE", "bfloat16")
    quant = json.loads(os.environ.get("BENCH_QUANT", "{}") or "{}")
    hidden = int(os.environ.get("BENCH_LOWPREC_HIDDEN", "1024"))
    depth = int(os.environ.get("BENCH_LOWPREC_DEPTH", "8"))
    n_dev = max(1, jax.local_device_count())
    batch = BATCH - (BATCH % n_dev) or n_dev

    def build(policy: bool):
        RandomGenerator.set_seed(1)
        layers = [nn.Linear(64, hidden), nn.Tanh()]
        for _ in range(depth):
            layers += [nn.Linear(hidden, hidden), nn.Tanh()]
        layers += [nn.Linear(hidden, 16), nn.LogSoftMax()]
        model = nn.Sequential(*layers)
        r = np.random.RandomState(0)
        x = r.randn(batch * 4, 64).astype(np.float32)
        y = (r.rand(batch * 4) * 16).astype(np.int32)
        ds = DataSet.distributed(
            DataSet.array(x, y, batch_size=batch), n_dev
        )
        kw = {}
        if policy:
            kw = dict(
                comms_dtype=comms,
                error_feedback=bool(quant.get("error_feedback", True)),
                master_dtype=quant.get("master_dtype"),
                slot_dtype=quant.get("slot_dtype"),
            )
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                              parameter_sync="sharded", **kw)
        opt.set_optim_method(SGD(learningrate=0.05, momentum=0.9))
        opt.set_end_when(Trigger.max_iteration(WARMUP_STEPS + MEASURE_STEPS))
        return opt

    def run(policy: bool):
        from bigdl_tpu.obs import Telemetry

        opt = build(policy)
        tel = Telemetry()
        opt.set_telemetry(tel)
        opt.optimize()
        # steady-state step time from the telemetry stream's per-step wall
        # (median of the post-warmup steps) — the one SPMD compile must not
        # ride the headline, and policy-on/off compile DIFFERENT programs,
        # so a compile-inclusive wall would compare compile times
        walls = sorted(
            r["wall_s"] for r in tel.ring.steps()[WARMUP_STEPS:]
            if r.get("wall_s")
        )
        wall = walls[len(walls) // 2] if walls else 0.0
        # lower the REAL cached step and count collective operand bytes
        fp = opt._flat_fp
        method = opt.optim_method
        pol = opt._precision
        mdtype = jnp.float32
        if pol is not None and pol.master_dtype is not None:
            mdtype = pol.master_dtype
        p0 = jax.ShapeDtypeStruct((fp.padded_total,), mdtype)
        slots = jax.eval_shape(
            method.init_slots,
            jax.ShapeDtypeStruct((fp.padded_total,), jnp.float32),
        )
        if pol is not None and pol.slot_dtype is not None:
            slots = {k: jax.ShapeDtypeStruct(v.shape, pol.slot_dtype)
                     for k, v in slots.items()}
        args = [p0,
                jax.eval_shape(lambda: jax.tree_util.tree_map(
                    jnp.asarray, opt.model.get_state())),
                slots]
        if pol is not None and pol.comms_dtype is not None \
                and pol.error_feedback:
            args.append(jax.ShapeDtypeStruct(
                (n_dev, fp.padded_total), jnp.float32))
        args += [jax.ShapeDtypeStruct((batch, 64), jnp.float32),
                 jax.ShapeDtypeStruct((batch,), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.float32),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((2,), jnp.uint32)]
        coll = collective_bytes(opt._jit_step.lower(*args))
        return wall, coll

    base_wall, base_coll = run(policy=False)
    pol_wall, pol_coll = run(policy=True)
    device = jax.devices()[0]
    ratio = (
        base_coll["grad_exchange_bytes"] / pol_coll["grad_exchange_bytes"]
        if pol_coll["grad_exchange_bytes"] else None
    )
    result = {
        "metric": f"low-precision flat path step ms ({comms} comms, "
                  f"{n_dev} dev, {hidden}x{depth} MLP, batch {batch})",
        "value": round(pol_wall * 1e3, 3),
        "unit": "ms/step",
        "vs_baseline": None,
        "baseline_step_ms": round(base_wall * 1e3, 3),
        "comms_dtype": comms,
        "quant": quant,
        "grad_exchange_bytes": pol_coll["grad_exchange_bytes"],
        "grad_exchange_bytes_f32": base_coll["grad_exchange_bytes"],
        "grad_exchange_reduction_x": None if ratio is None else round(ratio, 2),
        "collective_bytes": pol_coll["by_op"],
        "collective_bytes_f32": base_coll["by_op"],
        "device_kind": device.device_kind,
        "platform": device.platform,
        "backend": jax.default_backend(),
    }
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    if os.path.isdir(art_dir):
        with open(os.path.join(art_dir, "LOWPREC_r01.json"), "w") as f:
            json.dump(result, f, indent=1)
    return result


def _measure_serving() -> dict:
    """BENCH_MODE=serving: end-to-end serving latency/throughput through the
    production serving runtime (bigdl_tpu/serving) — flagship model hosted by
    a ModelServer, single-record requests from BENCH_SERVE_CLIENTS threads
    through the continuous batcher. Headline: requests/sec/chip, with
    p50/p99 END-TO-END latency (enqueue -> caller materialization) riding
    along — the serving twin of the training headline."""
    import threading

    import jax
    import numpy as np

    from bigdl_tpu.models import flagship_model
    from bigdl_tpu.serving import ModelServer
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(1)
    Engine.set_compute_dtype(os.environ.get("BENCH_COMPUTE_DTYPE", "bfloat16"))
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", "8"))
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "1024"))
    max_delay_ms = float(os.environ.get("BENCH_SERVE_MAX_DELAY_MS", "5"))
    model, x, _, name = flagship_model(batch=BATCH, stem="conv7")
    model.init(sample_input=x)
    records = np.asarray(x)

    server = ModelServer()
    server.register(
        "flagship", model, sample_input=records[0],
        batch_size=BATCH, max_delay_ms=max_delay_ms,
    )
    warmup_s = server.models()["flagship"]["warmup_s"]

    lat_lock = threading.Lock()
    latencies: list = []

    def client(k: int) -> None:
        gen = np.random.default_rng(k)
        # spread the remainder so exactly n_requests are served whatever
        # the client count
        n_mine = n_requests // clients + (1 if k < n_requests % clients else 0)
        for _ in range(n_mine):
            fut = server.infer("flagship",
                               records[int(gen.integers(len(records)))])
            fut.result()
            with lat_lock:
                latencies.append(fut.spans()["total_s"])

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(k,)) for k in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    served = len(latencies)
    # read the ring AFTER close(): it joins the batcher threads, so the
    # final flush's serve record is guaranteed in (no undercounted fill)
    server.close()
    serves = [r for r in server.telemetry.ring.records
              if r.get("type") == "serve"]
    fill = (
        sum(float(r["batch_fill"]) for r in serves) / len(serves)
        if serves else None
    )

    if not latencies:
        raise RuntimeError(
            f"serving bench served 0 requests (BENCH_SERVE_REQUESTS="
            f"{n_requests}, clients={clients}); raise the request budget"
        )
    # same nearest-rank convention as the serve records / obs_report, so
    # the headline artifact and the telemetry stream agree on identical data
    from bigdl_tpu.serving.batcher import _nearest_rank

    lats = sorted(latencies)
    p50 = _nearest_rank(lats, 50) * 1e3
    p99 = _nearest_rank(lats, 99) * 1e3
    n_dev = max(1, jax.local_device_count())
    rps = served / elapsed
    device = jax.devices()[0]
    result = {
        "metric": f"{name} serving requests/sec/chip (continuous batcher, "
                  f"batch {BATCH}, {clients} clients, "
                  f"max_delay {max_delay_ms}ms)",
        "value": round(rps / n_dev, 2),
        "unit": "requests/sec/chip",
        "vs_baseline": None,
        "requests": served,
        "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3),
        "batch_fill_mean": None if fill is None else round(fill, 4),
        "n_flushes": len(serves),
        "warmup_s": round(warmup_s, 3),
        "clients": clients,
        "batch": BATCH,
        "device_kind": device.device_kind,
        "platform": device.platform,
        # explicit backend flag (carried ROADMAP leftover): CPU-only serving
        # numbers must be recognizable as such in the artifact itself
        "backend": jax.default_backend(),
    }
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    if os.path.isdir(art_dir):
        with open(os.path.join(art_dir, "SERVING_r01.json"), "w") as f:
            json.dump(result, f, indent=1)
    return result


def _measure_transformer() -> dict:
    """Transformer-LM training throughput (BENCH_MODE=transformer) with the
    Pallas flash-attention kernel IN-GRAPH (auto-selected by
    ``scaled_dot_product_attention``; VERDICT r2 #3), A/B'd against the dense
    XLA path on the identical model."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(1)
    Engine.set_compute_dtype(os.environ.get("BENCH_COMPUTE_DTYPE", "bfloat16"))
    act_dtype = os.environ.get("BENCH_ACT_DTYPE", "bfloat16")
    if act_dtype != "float32":
        Engine.set_activation_dtype(act_dtype)

    seq_len = int(os.environ.get("BENCH_SEQ_LEN", "2048"))
    batch = int(os.environ.get("BENCH_LM_BATCH", "8"))
    vocab = 8192
    # dropout=0 so the flash auto-selection condition holds during training
    model = nn.Transformer(
        vocab_size=vocab, hidden_size=512, num_heads=8, filter_size=2048,
        num_hidden_layers=6, postprocess_dropout=0.0, attention_dropout=0.0,
        relu_dropout=0.0, mode="lm",
    )
    criterion = nn.CrossEntropyCriterion()
    method = SGD(learningrate=0.1)
    gen = np.random.default_rng(0)
    ids = jnp.asarray(gen.integers(0, vocab, (batch, seq_len)))
    targets = jnp.asarray(gen.integers(0, vocab, (batch * seq_len,)))
    params, state = model.init(sample_input=np.asarray(ids))
    rng = jax.random.PRNGKey(0)

    def run(tag):
        os.environ["BIGDL_ATTN_IMPL"] = tag

        @partial(jax.jit, donate_argnums=(0,))
        def train_step(params, slots, ids, t, rng):
            def loss_fn(p):
                y, _ = model.apply(p, state, ids, training=True, rng=rng)
                return criterion._apply(y.reshape(-1, vocab), t)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            params, slots = method.update(
                grads, params, slots, jnp.asarray(0.1), jnp.asarray(1)
            )
            return params, slots, loss

        p = jax.tree_util.tree_map(lambda a: a.copy(), params)
        slots = method.init_slots(p)
        for _ in range(WARMUP_STEPS):
            p, slots, loss = train_step(p, slots, ids, targets, rng)
        float(loss)
        windows = []
        for _ in range(MEASURE_WINDOWS):
            t0 = time.perf_counter()
            for _ in range(MEASURE_STEPS):
                p, slots, loss = train_step(p, slots, ids, targets, rng)
            float(loss)
            windows.append(time.perf_counter() - t0)
        windows.sort()
        elapsed = windows[len(windows) // 2]
        return batch * seq_len * MEASURE_STEPS / elapsed, float(loss)

    flash_tps, flash_loss = run("flash")
    dense_tps, dense_loss = run("dense")
    os.environ.pop("BIGDL_ATTN_IMPL", None)
    device = jax.devices()[0]
    return {
        "metric": f"Transformer-LM train tokens/sec/chip (flash in-graph, "
                  f"T={seq_len}, batch {batch}, act={act_dtype})",
        "value": round(flash_tps, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,
        "dense_tokens_per_sec": round(dense_tps, 2),
        "flash_vs_dense": round(flash_tps / dense_tps, 3),
        "flash_loss": round(flash_loss, 4),
        "dense_loss": round(dense_loss, 4),
        "device_kind": device.device_kind,
        "platform": device.platform,
    }


def _measure_pipeline() -> dict:
    """BENCH_MODE=pipeline: pipeline-parallel training throughput through the
    PRODUCTION optimizer path (parallel.PipelineOptimizer over nn.
    PipelinedBlocks); BENCH_MOE=1 swaps in the expert-parallel path
    (ExpertParallelOptimizer over nn.MoE). When the device count exceeds the
    stage/expert count the remainder becomes a data axis (dp x pp / dp x ep).
    The artifact carries the schedule economics next to the headline:
    ``pipe_bubble_frac`` and the ppermute/all_to_all comms decomposition off
    the run's own perf records — the same fields a training fleet's
    telemetry reports, so bench and production can never disagree.

    Needs >= 2 devices (one per stage/expert); on CPU set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``."""
    import jax
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.obs import Telemetry
    from bigdl_tpu.obs.perf import PerfConfig
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.parallel import (
        ExpertParallelOptimizer,
        PipelineOptimizer,
        make_mesh,
    )
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(1)
    moe = os.environ.get("BENCH_MOE") == "1"
    n_dev = len(jax.devices())
    stages = min(int(os.environ.get("BENCH_PP_STAGES", "4")), n_dev)
    if stages < 2:
        raise RuntimeError(
            "BENCH_MODE=pipeline needs >= 2 devices (one per "
            f"{'expert' if moe else 'stage'}); have {n_dev}")
    dp = n_dev // stages
    axis = "expert" if moe else "pipe"
    devices = jax.devices()[: dp * stages]
    if dp > 1:
        mesh, data_axis = make_mesh({"data": dp, axis: stages},
                                    devices=devices), "data"
    else:
        mesh, data_axis = make_mesh({axis: stages}, devices=devices), None

    hidden = int(os.environ.get("BENCH_PP_HIDDEN", "1024"))
    batch = int(os.environ.get("BENCH_PP_BATCH", str(BATCH)))
    classes = 1000
    steps = WARMUP_STEPS + MEASURE_STEPS
    gen = np.random.default_rng(0)
    x = gen.standard_normal((batch * steps, hidden)).astype(np.float32)
    y = gen.integers(0, classes, batch * steps)
    ds = DataSet.array(x, y, batch_size=batch)
    crit = nn.ClassNLLCriterion()
    if moe:
        model = nn.Sequential(
            nn.Linear(hidden, hidden),
            nn.MoE(stages, ffn_size=4 * hidden, capacity_factor=2.0),
            nn.Linear(hidden, classes), nn.LogSoftMax())
        opt = ExpertParallelOptimizer(model, ds, crit, mesh=mesh,
                                      data_axis=data_axis)
    else:
        n_micro = int(os.environ.get("BENCH_PP_MICRO", "0")) or None
        stage = nn.Sequential(nn.Linear(hidden, 4 * hidden), nn.Tanh(),
                              nn.Linear(4 * hidden, hidden))
        model = nn.Sequential(
            nn.Linear(hidden, hidden),
            nn.PipelinedBlocks(stage, stages, n_micro=n_micro),
            nn.Linear(hidden, classes), nn.LogSoftMax())
        opt = PipelineOptimizer(model, ds, crit, mesh=mesh,
                                data_axis=data_axis, n_micro=n_micro)
    tel = Telemetry()
    opt.set_optim_method(SGD(learningrate=0.05, momentum=0.9))
    opt.set_telemetry(tel)
    opt.set_perf(PerfConfig(every_n_steps=5, baseline_steps=2, window=5,
                            capture=False))
    opt.set_end_when(Trigger.max_iteration(steps))
    opt.optimize()

    # steady-state wall off the telemetry stream (median post-warmup step);
    # the one compile must not ride the headline
    walls = sorted(r["wall_s"] for r in tel.ring.steps()[WARMUP_STEPS:]
                   if r.get("wall_s"))
    wall = walls[len(walls) // 2] if walls else 0.0
    perfs = [r for r in tel.ring.records if r["type"] == "perf"]
    last = perfs[-1] if perfs else {}
    n_chips = int(mesh.devices.size)
    tput = batch / wall / n_chips if wall else None
    path = ("dp x ep" if (moe and dp > 1) else "ep" if moe
            else "dp x pp" if dp > 1 else "pp")
    unit = ("tokens" if moe else "rows") + "/sec/chip"
    device = jax.devices()[0]
    return {
        "metric": (f"{'MoE' if moe else 'pipeline'} train throughput "
                   f"({path}, {stages} {'experts' if moe else 'stages'}"
                   + (f", dp={dp}" if dp > 1 else "")
                   + f", hidden {hidden}, batch {batch})"),
        "value": round(tput, 2) if tput else None,
        "unit": unit,
        "vs_baseline": None,
        "step_ms": round(wall * 1e3, 3),
        "pipe_bubble_frac": last.get("pipe_bubble_frac"),
        "ppermute_bytes": last.get("ppermute_bytes"),
        "all_to_all_bytes": last.get("all_to_all_bytes"),
        "collective_bytes": last.get("collective_bytes"),
        "compiles": tel.compile_count,
        "device_kind": device.device_kind,
        "platform": device.platform,
        "backend": jax.default_backend(),
    }


def _measure() -> dict:
    """Default mode: build flagship model, time the jitted train step."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.models import flagship_model
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    # XLA scheduler surface (docs/performance.md): BENCH_XLA_FLAGS carries a
    # JSON dict of validated Engine knobs, applied BEFORE the first backend
    # touch below; the config artifact reports them (Engine.xla_flags())
    bench_xla = os.environ.get("BENCH_XLA_FLAGS")
    if bench_xla:
        Engine.set_xla_flags(json.loads(bench_xla))
    RandomGenerator.set_seed(1)
    dtype = os.environ.get("BENCH_COMPUTE_DTYPE", "bfloat16")
    Engine.set_compute_dtype(dtype)
    # end-to-end bf16 activations (fp32 master params/BN stats) — the round-3
    # default; BENCH_ACT_DTYPE=float32 reverts to the fp32 residual stream
    act_dtype = os.environ.get("BENCH_ACT_DTYPE", "bfloat16")
    if act_dtype != "float32":
        Engine.set_activation_dtype(act_dtype)
    # fused Pallas kernel toggle (docs/performance.md): BENCH_FUSED_KERNELS=1
    # routes LayerNorm/RMSNorm + bias/activation epilogues through ops/
    from bigdl_tpu.utils.engine import env_flag

    if env_flag("BENCH_FUSED_KERNELS"):
        Engine.set_fused_kernels(True)
    stem = os.environ.get("BENCH_STEM", "s2d")  # s2d | conv7
    model, x, labels, name = flagship_model(batch=BATCH, stem=stem)
    criterion = nn.ClassNLLCriterion()
    method = SGD(learningrate=0.1, momentum=0.9)

    params, state = model.init(sample_input=x)
    slots = method.init_slots(params)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, state, slots, x, t, rng):
        def loss_fn(p):
            y, s = model.apply(p, state, x, training=True, rng=rng)
            return criterion._apply(y, t), s

        (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, slots = method.update(
            grads, params, slots, jnp.asarray(0.1), jnp.asarray(1)
        )
        return params, new_state, slots, loss

    xs, ts = jnp.asarray(x), jnp.asarray(labels)
    rng = jax.random.PRNGKey(0)

    # compile split out from steady-state, with the persistent-cache verdict:
    # a cache_hit=True round that still shows minutes of "compile" is a disk /
    # deserialization problem, not an XLA regression (and vice versa)
    from bigdl_tpu.utils import compat as _compat

    cache_before = _compat.compilation_cache_entries()
    t_compile0 = time.perf_counter()
    compiled = train_step.lower(params, state, slots, xs, ts, rng).compile()
    compile_s = time.perf_counter() - t_compile0
    cache_hit = _compat.compilation_cache_hit(
        cache_before, _compat.compilation_cache_entries()
    )
    step_flops = _step_flops(compiled)

    for _ in range(WARMUP_STEPS):
        params, state, slots, loss = train_step(params, state, slots, xs, ts, rng)
    # every timed region ends in a scalar pull: the device->host transfer
    # cannot complete before the whole dependency chain has run
    float(loss)

    windows = []
    dispatch_s_total = 0.0
    for _ in range(MEASURE_WINDOWS):
        t0 = time.perf_counter()
        for _ in range(MEASURE_STEPS):
            # per-call host dispatch time: steady-state async dispatch is the
            # host-side floor in front of each step — the dispatch-gap metric
            # (docs/performance.md); two perf_counter reads, no device sync
            td = time.perf_counter()
            params, state, slots, loss = train_step(
                params, state, slots, xs, ts, rng
            )
            dispatch_s_total += time.perf_counter() - td
        float(loss)
        windows.append(time.perf_counter() - t0)
    dispatch_gap_ms = round(
        dispatch_s_total / (MEASURE_WINDOWS * MEASURE_STEPS) * 1e3, 4
    )
    windows.sort()
    elapsed = windows[len(windows) // 2]  # median window

    images_per_sec = MEASURE_STEPS * BATCH / elapsed
    step_ms = elapsed / MEASURE_STEPS * 1e3

    device = jax.devices()[0]
    peak = _peak_flops(device.device_kind)
    mfu = None
    if step_flops and peak:
        mfu = round(step_flops / (elapsed / MEASURE_STEPS) / peak, 4)

    # health overhead: the same step additionally computing obs/health.py's
    # in-graph per-layer statistics (what `set_health` costs at stride 1) —
    # one extra window, reported as a % on the headline artifact and mirrored
    # into the telemetry stream as a `health` record
    from bigdl_tpu.obs.health import HealthConfig, HealthMonitor

    hm = HealthMonitor(HealthConfig())
    hm.bind_tree(params)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step_health(params, state, slots, x, t, rng):
        def loss_fn(p):
            y, s = model.apply(p, state, x, training=True, rng=rng)
            return criterion._apply(y, t), s

        (loss, new_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        new_params, new_slots = method.update(
            grads, params, slots, jnp.asarray(0.1), jnp.asarray(1)
        )
        return new_params, new_state, new_slots, loss, hm.tree_stats(
            grads, params, new_params, new_state
        )

    for _ in range(WARMUP_STEPS):
        params, state, slots, loss, hstats = train_step_health(
            params, state, slots, xs, ts, rng
        )
    float(loss)
    t0 = time.perf_counter()
    for _ in range(MEASURE_STEPS):
        params, state, slots, loss, hstats = train_step_health(
            params, state, slots, xs, ts, rng
        )
    float(loss)
    h_elapsed = time.perf_counter() - t0
    health_step_ms = round(h_elapsed / MEASURE_STEPS * 1e3, 2)
    health_overhead_pct = round(
        100.0 * (health_step_ms - step_ms) / step_ms, 2
    )
    health_sample = hm.record_fields(hm.snapshot(hstats))

    # train_step is a single-device jit: it runs on ONE chip regardless of how
    # many are attached, so per-chip == measured (no division by device count)
    return {
        "metric": f"{name} train images/sec/chip (batch {BATCH}, {dtype}, "
                  f"act={act_dtype}, stem={stem})",
        "value": round(images_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": None,
        "step_ms": round(step_ms, 2),
        "window_step_ms": [round(w / MEASURE_STEPS * 1e3, 2) for w in windows],
        "compile_seconds": round(compile_s, 2),
        "compile_cache_hit": cache_hit,
        "compile_cache_dir": Engine.compilation_cache_dir(),
        "step_flops": step_flops,
        "mfu": mfu,
        # same cost model as the live telemetry perf records (obs/perf.py +
        # the shared compat.device_peaks table) — the two figures agreeing
        # is the join's sanity check, and perf_gate reads either
        "mfu_estimate": _mfu_estimate(
            step_flops, elapsed / MEASURE_STEPS, device.device_kind
        ),
        "health_step_ms": health_step_ms,
        "health_overhead_pct": health_overhead_pct,
        "health_sample": health_sample,
        "activation_dtype": act_dtype,
        "stem": stem,
        # MFU-campaign config surface (docs/performance.md): the fused-kernel
        # toggle, the per-step host dispatch-gap, and the XLA scheduler flags
        # Engine manages — the artifact records the exact perf configuration
        "fused_kernels": Engine.fused_kernels(),
        "dispatch_gap_ms": dispatch_gap_ms,
        "xla_flags": Engine.xla_flags() or None,
        "device_kind": device.device_kind,
        "platform": device.platform,
    }


def _write_bench_telemetry(result: dict) -> None:
    """Emit the measurement as a telemetry JSONL stream under
    ``bench_artifacts/telemetry/<mode>.jsonl`` (schema:
    docs/observability.md), so every BENCH round carries the unified
    observability artifact — step walls per measurement window, the compile
    event, and (on the real chip) the HBM watermark — readable later with
    ``python tools/obs_report.py``."""
    art = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_artifacts"
    )
    if not os.path.isdir(art):
        return
    from bigdl_tpu.obs import JsonlExporter, Telemetry

    mode = os.environ.get("BENCH_MODE", "") or "headline"
    path = os.path.join(art, "telemetry", f"{mode}.jsonl")
    if os.path.exists(path):
        os.remove(path)  # one stream per round, newest wins
    tel = Telemetry(exporters=[JsonlExporter(path)])
    tel.run_started(f"bench:{mode}", metric=result.get("metric"))

    def emit(d: dict, label: str) -> None:
        comp = d.get("compile_seconds")
        if comp is not None:
            tel.compile_event(iteration=0, seconds=float(comp),
                              path=label)
        batch = int(d.get("batch", BATCH))
        windows = d.get("window_step_ms")
        if not windows and d.get("step_ms"):
            windows = [d["step_ms"]]
        for i, step_ms in enumerate(windows or [], 1):
            tel.step(
                path=label,
                iteration=i,
                records=batch * MEASURE_STEPS,
                wall_s=step_ms / 1e3 * MEASURE_STEPS,
                records_per_sec=batch * 1e3 / step_ms if step_ms else None,
            )
        # the health-overhead window's last in-graph statistics snapshot
        # (obs/health.py), so the bench artifact carries a model-health
        # baseline readable by tools/health_report.py
        sample = d.get("health_sample")
        if sample:
            tel.health(
                iteration=len(windows or []) or 1,
                path=label,
                **sample,
            )

    if result.get("rows"):  # configs mode: one stream, per-config labels
        for row in result["rows"]:
            emit(row, str(row.get("config", mode)))
    else:
        emit(result, mode)
    tel.run_ended(f"bench:{mode}", value=result.get("value"))
    tel.close()


def main() -> None:
    from bigdl_tpu.obs import blackbox
    from bigdl_tpu.utils.engine import Engine

    # persistent compile cache (JAX_COMPILATION_CACHE_DIR, else the fixed
    # in-checkout default): the NEXT bench run on this host deserializes the
    # XLA binaries instead of recompiling
    Engine.ensure_compilation_cache()
    # flight recorder + hard-crash hook (obs/blackbox.py): a run that dies
    # leaves faulthandler stacks and a sealed bundle under the run dir
    blackbox.ensure_armed()
    body = {
        "files": _measure_files,
        "flash": _measure_flash,
        "transformer": _measure_transformer,
        "configs": _measure_configs,
        "int8": _measure_int8,
        "lowprec": _measure_lowprec,
        "pipeline": _measure_pipeline,
        "serving": _measure_serving,
    }.get(os.environ.get("BENCH_MODE", ""), _measure)
    try:
        result = body()
    except BaseException as e:
        if not isinstance(e, KeyboardInterrupt):
            blackbox.dump_postmortem(f"bench_{type(e).__name__}", error=e)
        raise  # total failure: traceback + non-zero exit, never a null JSON
    _write_bench_telemetry(result)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
