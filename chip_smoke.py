"""chip_smoke.py — the standing proof that the program starts on the chip.

One process drives the system's main path once through the entry points a
user calls, at the full width of the model the north star names (ResNet-50,
1000 classes, 3x224x224, batch 128 per chip, bf16 operands and activations):

  A  device    the platform is ``tpu`` and the peaks table knows the chip
  B  train     LocalOptimizer.optimize() on one chip, telemetry attached
  C  distri    DistriOptimizer(parameter_sync="sharded") — the ZeRO-1
               shard_map step — over every local chip
  D  kernels   every Pallas kernel behind a public switch, compiled by Mosaic
               and checked against its reference; flash attention once more
               inside a real nn.Transformer LocalOptimizer step
  E  serving   ModelServer over phase B's model: 32 concurrent single-record
               requests, bit-equal to the serial Predictor, zero compiles
               after warm-up
  F  readback  the prefetch seam with recycled host batch buffers: every
               device batch, pulled back after later batches were gathered,
               is bitwise the rows the epoch's order names; on one chip
               (device_put) and over every local chip (DistriOptimizer's
               place_pair)

Nothing is caught and carried past: any failed phase raises and the exit code
is non-zero. On success the last stdout line is one JSON object
``{"ok": true, "device": {...}}``. There is no platform handling here on
purpose — under ``JAX_PLATFORMS=cpu`` phase A fails, which is the point.

    python chip_smoke.py                      # what the driver runs
    python chip_smoke.py --expect-cache-hit   # second run on one cache dir:
                                              # phase B must compile nothing
                                              # fresh; prints both walls

The phase functions take their sizes as arguments so tests/test_chip_smoke.py
can rehearse the control flow at tiny size on the CPU; ``main()`` always runs
the full width and always demands the chip.
"""

from __future__ import annotations

import argparse
import collections
import importlib.metadata
import json
import os
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
# the previous run's phase-B compile record, so the second of two runs on one
# cache dir can print both compile walls (chiprun_out/ is what a chip call
# brings back; gitignored)
LAST_RUN = os.path.join(REPO, "chiprun_out", "chip_smoke_last.json")
MOSAIC_CALL = "tpu_custom_call"


def log(msg: str) -> None:
    print(msg, flush=True)


def on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def assert_mosaic(lowered_text: str, what: str) -> int:
    """The lowered program must carry the Mosaic custom call — an interpreted
    expansion of the kernel would pass every numeric check. Only the TPU
    lowers to it; the CPU rehearsal runs the interpreter by design."""
    n = lowered_text.count(MOSAIC_CALL)
    if on_tpu() and n == 0:
        raise AssertionError(f"{what}: no {MOSAIC_CALL} in the lowered program")
    return n


# ------------------------------------------------------------------ A: device
def phase_device() -> dict:
    import jax
    import jaxlib

    devs = jax.devices()
    d = devs[0]
    log(f"platform: {d.platform}")
    log(f"device_kind: {d.device_kind}")
    log(f"device_count: {len(devs)}")
    log(f"versions: jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {importlib.metadata.version('libtpu')}")
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU — jax found platform {d.platform!r} "
            f"({d.device_kind}); this check only passes on the chip")
    from bigdl_tpu.utils.compat import device_peaks

    peaks = device_peaks()  # raises for a chip the table does not know
    log(f"phase A device: ok ({peaks!r})")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def build_native() -> bool:
    """Build the host library from the files git commits. A leftover binary
    is removed first: csrc/*.so is gitignored, so the driver's checkout never
    has one and a run that silently loaded it would take another host path."""
    from bigdl_tpu import native

    so = os.path.join(REPO, "csrc", "libbigdl_host.so")
    if os.path.exists(so):
        os.remove(so)
    built = native.build() and native.available()
    log(f"native: {'true' if built else 'false'}")
    return built


# ------------------------------------------------------------- B: one chip
def _image_set(n: int, image: int, classes: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 3, image, image)).astype(np.float32)
    y = rng.integers(0, classes, n)
    return x, y


def _check_losses(tel, iters: int, what: str):
    losses = [r["loss"] for r in tel.ring.steps()]
    if len(losses) != iters:
        raise AssertionError(f"{what}: {len(losses)} step records, want {iters}")
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{what}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{what}: loss did not fall: {losses}")
    return losses


def _sync_two_ways(opt, steps: int) -> None:
    """The step wall taken two ways over the same steps of the optimizer's
    own jitted step — ending in jax.block_until_ready and ending in
    float(loss). Plain information (does block_until_ready wait on this
    PJRT?), under no metric's name. The step is lowered against the geometry
    its first dispatch recorded and fed zeros: only the clock matters here."""
    import jax
    import jax.numpy as jnp

    step, specs = opt._step_export_info
    compiled = step.lower(*specs).compile()
    args = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), specs)

    def window(sync):
        nonlocal args
        t0 = time.perf_counter()
        for _ in range(steps):
            outs = compiled(*args)  # (params, model_state, slots, loss)
            args = tuple(outs[:3]) + tuple(args[3:])
        sync(outs)
        return (time.perf_counter() - t0) / steps * 1e3

    window(lambda outs: float(outs[3]))  # settle
    wall_block = window(jax.block_until_ready)
    wall_pull = window(lambda outs: float(outs[3]))
    log(f"  step wall over {steps} steps: {wall_block:.2f} ms ending in "
        f"block_until_ready, {wall_pull:.2f} ms ending in float(loss)")


def phase_train(depth=50, classes=1000, image=224, batch=128, iters=10,
                expect_cache_hit=False):
    """ResNet through LocalOptimizer.optimize() on one chip, as users run it:
    telemetry (and with it PerfAccountant + FlightRecorder) attached."""
    import jax

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.models import ResNet
    from bigdl_tpu.obs import Telemetry
    from bigdl_tpu.optim import SGD, LocalOptimizer, Trigger
    from bigdl_tpu.utils.compat import CacheDirWatch
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(1)
    Engine.set_compute_dtype("bfloat16")
    Engine.set_activation_dtype("bfloat16")
    model = ResNet(depth, class_num=classes, dataset="imagenet",
                   with_log_softmax=True)
    # two fixed batches, seen five times each: the loss must fall
    x, y = _image_set(2 * batch, image, classes)
    opt = LocalOptimizer(model, DataSet.array(x, y, batch_size=batch),
                         nn.ClassNLLCriterion())
    opt.set_optim_method(SGD(learningrate=0.02, momentum=0.9))
    opt.set_end_when(Trigger.max_iteration(iters))
    tel = Telemetry()
    opt.set_telemetry(tel)
    watch = CacheDirWatch()
    opt.optimize()
    fresh = watch.fresh_count()

    losses = _check_losses(tel, iters, "phase B")
    if tel.compile_count != 1:
        raise AssertionError(
            f"phase B: {tel.compile_count} train-step compiles, want 1")
    compile_s = next(r["seconds"] for r in tel.ring.records
                     if r["type"] == "compile")
    log(f"  train-step compile+first-dispatch wall {compile_s:.1f} s, "
        f"{fresh} fresh cache entries in {Engine.compilation_cache_dir()}")
    if expect_cache_hit:
        with open(LAST_RUN) as f:
            prev = json.load(f)
        log(f"  previous run: compile wall {prev['compile_s']:.1f} s, "
            f"{prev['fresh_entries']} fresh entries")
        if fresh != 0:
            raise AssertionError(
                f"phase B: expected a warm compile cache, but optimize() "
                f"persisted {fresh} fresh entries")
    stats = jax.local_devices()[0].memory_stats()
    if on_tpu() and not stats["peak_bytes_in_use"] > 0:
        raise AssertionError(f"phase B: no device memory in use: {stats}")
    _sync_two_ways(opt, steps=iters)
    tel.close()
    log(f"phase B train: ok (ResNet-{depth} b{batch}, loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}, 1 compile, peak "
        f"{(stats or {}).get('peak_bytes_in_use', 0) / 2**30:.2f} GiB)")
    return model, {"compile_s": compile_s, "fresh_entries": fresh}


# ------------------------------------------------------ C: every local chip
def phase_distri(depth=50, classes=1000, image=224, batch_per_chip=128,
                 iters=6):
    """The same model through the ZeRO-1 sharded DistriOptimizer over all of
    jax.local_devices(). With one chip this is the one-device mesh — the
    mesh that exists, not a fall-back."""
    import jax

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.models import ResNet
    from bigdl_tpu.obs import Telemetry
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    devices = jax.local_devices()
    n = len(devices)
    Engine.init(devices=devices)
    RandomGenerator.set_seed(1)
    batch = batch_per_chip * n
    x, y = _image_set(2 * batch, image, classes)
    model = ResNet(depth, class_num=classes, dataset="imagenet",
                   with_log_softmax=True)
    opt = DistriOptimizer(
        model, DataSet.distributed(DataSet.array(x, y, batch_size=batch), n),
        nn.ClassNLLCriterion(), parameter_sync="sharded")
    opt.set_optim_method(SGD(learningrate=0.02, momentum=0.9))
    opt.set_end_when(Trigger.max_iteration(iters))
    tel = Telemetry()
    opt.set_telemetry(tel)
    opt.optimize()
    losses = _check_losses(tel, iters, "phase C")
    if tel.compile_count != 1:
        raise AssertionError(
            f"phase C: {tel.compile_count} SPMD-step compiles, want 1")

    # what the step carried, as its first dispatch recorded it: the flat
    # master vector committed to every device, the slot vectors sharded
    step, specs = opt._step_export_info
    flat, slots = specs[0], specs[2]
    if len(flat.sharding.device_set) != n:
        raise AssertionError(f"phase C: flat master on {flat.sharding}")
    for name, s in slots.items():
        if len(s.sharding.device_set) != n or (
                n > 1 and s.sharding.is_fully_replicated):
            raise AssertionError(f"phase C: slot {name} on {s.sharding}")
    hlo = step.lower(*specs).as_text()
    collectives = {c: (c in hlo or c.replace("_", "-") in hlo)
                   for c in ("reduce_scatter", "all_gather")}
    if n > 1 and not all(collectives.values()):
        raise AssertionError(f"phase C: collectives in step: {collectives}")
    log(f"  devices: {n}; flat master {flat.shape} on "
        f"{len(flat.sharding.device_set)} devices, slots sharded "
        f"{next(iter(slots.values())).sharding.spec}; collectives {collectives}")
    for d in devices:
        stats = d.memory_stats()
        log(f"  {d.platform}:{d.id} bytes_in_use "
            f"{(stats or {}).get('bytes_in_use', 0)} peak "
            f"{(stats or {}).get('peak_bytes_in_use', 0)}")
        if on_tpu() and not stats["bytes_in_use"] > 0:
            raise AssertionError(f"phase C: nothing in use on {d}: {stats}")
    tel.close()
    log(f"phase C distri: ok (ZeRO-1 ResNet-{depth} b{batch_per_chip}/chip on "
        f"{n} device(s), loss {losses[0]:.4f} -> {losses[-1]:.4f}, 1 compile)")


# --------------------------------------------------------------- D: kernels
def _fwd_bwd(fn, args, cot):
    """(lowered text, (output, input cotangents)) of ``fn`` jitted over
    ``args`` and pulled back along ``cot`` — compared elementwise, because a
    scalar sum of millions of bf16-rounded terms is all rounding noise."""
    import jax

    def run(*a):
        out, vjp = jax.vjp(fn, *a)
        return out, vjp(cot.astype(out.dtype))

    jitted = jax.jit(run)
    return jitted.lower(*args).as_text(), jitted(*args)


def _close(got, want, tol: float, what: str) -> None:
    """tests/test_kernel_parity.py's rule over a pytree: every leaf finite
    and |Δ| ≤ tol · (1 + max|ref|). Reduced on the device; scalars come back."""
    import jax
    import jax.numpy as jnp

    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(g - w)))
        scale = 1.0 + float(jnp.max(jnp.abs(w)))
        if not (bool(jnp.all(jnp.isfinite(g))) and err <= tol * scale):
            raise AssertionError(
                f"{what}: max |Δ| = {err:.3g} > {tol} * {scale:.3g}")


# f32 is looser than the CPU lock (1e-5): Mosaic and XLA:TPU round
# rsqrt/tanh differently; bf16 is the parity suite's own 8-bit-mantissa band
F32_TOL, BF16_TOL = 1e-4, 5e-2


def _normal(seed: int, shape, dtype):
    """Operands made on the device: the widest one here is 100M elements."""
    import jax

    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def _flash_fwd_bwd(fn, args, cot, what: str):
    """``_fwd_bwd`` of a function that calls the flash kernel: (lowered text,
    results, a line for the log: the Mosaic calls and which backward the
    traced call got). The ``flash_tiles`` record of ops/flash_attention.py
    is held against the lowered program: the one backward kernel is one call
    beside the forward's, the pair two."""
    from bigdl_tpu.ops.flash_attention import take_tile_records

    take_tile_records()
    text, got = _fwd_bwd(fn, args, cot)
    n_calls = assert_mosaic(text, what)
    records = take_tile_records()
    if not records:  # off the TPU impl='flash' is the dense path
        return text, got, f"{n_calls} Mosaic calls, no kernel traced"
    record, = records
    want = 2 if record["backward"] == "fused" else 3
    if on_tpu() and n_calls != want:
        raise AssertionError(
            f"{what}: {n_calls} Mosaic calls with the backward "
            f"{record['backward']!r}; want {want}")
    return text, got, (
        f"{n_calls} Mosaic calls, backward {record['backward']}, dK/dV "
        f"accumulator {record['backward_acc_bytes'] / 2 ** 20:.1f} MiB")


def check_flash(t: int, d: int, n: int = 2, h: int = 4, hkv: int = None,
                window: int = None, lengths: bool = True) -> None:
    """The flash kernel, forward and backward, against the dense path:
    outputs and the gradients of q, k and v; with ``hkv`` grouped K/V heads,
    with ``window`` a sliding window."""
    import jax.numpy as jnp

    from bigdl_tpu.nn.attention import scaled_dot_product_attention as sdpa

    hkv = hkv or h
    q = _normal(t, (n, h, t, d), jnp.bfloat16)
    k, v = (_normal(t + i, (n, hkv, t, d), jnp.bfloat16) for i in (1, 2))
    lens = jnp.asarray(
        np.random.default_rng(t).integers(t // 2, t + 1, n), jnp.int32) \
        if lengths else None
    cot = _normal(t + 3, (n, h, t, d), jnp.float32)

    def attend(impl):
        return lambda q, k, v: sdpa(q, k, v, impl=impl, causal=True,
                                    lengths=lens, mask_q=True, window=window)

    what = (f"flash attention T={t} d={d} heads {h}/{hkv} bf16 causal"
            + (f" window {window}" if window else "")
            + ("+lengths" if lengths else ""))
    _, got, ran = _flash_fwd_bwd(attend("flash"), (q, k, v), cot, what)
    _close(got, _fwd_bwd(attend("dense"), (q, k, v), cot)[1], BF16_TOL, what)
    log(f"  {what}: fwd+bwd match impl='dense' ({ran})")


def check_flash_latent(t: int = 8192, d: int = 192, d_v: int = 128,
                       n: int = 1, h: int = 8) -> None:
    """The flash kernel at latent attention's head sizes (q and k 192, v and
    the output 128: JoyAI-LLM-Flash's), full causal, against the dense path:
    outputs and the gradients of q, k and v."""
    import jax.numpy as jnp

    from bigdl_tpu.nn.attention import scaled_dot_product_attention as sdpa

    q, k = (_normal(t + i, (n, h, t, d), jnp.bfloat16) for i in range(2))
    v = _normal(t + 2, (n, h, t, d_v), jnp.bfloat16)
    cot = _normal(t + 3, (n, h, t, d_v), jnp.float32)

    def attend(impl):
        return lambda q, k, v: sdpa(q, k, v, impl=impl, causal=True,
                                    mask_q=True)

    what = f"flash attention T={t} q/k {d} v {d_v} bf16 causal"
    _, got, ran = _flash_fwd_bwd(attend("flash"), (q, k, v), cot, what)
    _close(got, _fwd_bwd(attend("dense"), (q, k, v), cot)[1], BF16_TOL, what)
    log(f"  {what}: fwd+bwd match impl='dense' ({ran})")


def check_flash_remat(t: int, d: int, n: int = 2, heads: int = 4) -> None:
    """A GroupedQueryAttention under nn.Remat against the same module bare:
    the same outputs and gradients, and as many Mosaic calls in the program
    (the forward kernel once, then the backward's): the kernel's output and
    logsumexp are kept across the boundary, so the backward does not run
    flash_fwd again (utils/remat_keep.py)."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu import nn
    from bigdl_tpu.nn.decoder import GroupedQueryAttention

    attn = GroupedQueryAttention(heads, heads // 2, d)
    x = _normal(t, (n, t, heads * d), jnp.float32)
    attn.build(jax.random.PRNGKey(t), jax.ShapeDtypeStruct(x.shape, x.dtype))
    params, state = attn.get_parameters(), attn.get_state()
    wrapped = nn.Remat(attn)
    cot = _normal(t + 1, x.shape, jnp.float32)
    what = f"nn.Remat(flash attention) T={t} d={d}"
    text, got, ran = _flash_fwd_bwd(
        lambda p, x: wrapped.apply({attn.name(): p}, {attn.name(): state}, x)[0],
        (params, x), cot, what)
    bare, want = _fwd_bwd(lambda p, x: attn.apply(p, state, x)[0],
                          (params, x), cot)
    n_calls, n_bare = text.count(MOSAIC_CALL), bare.count(MOSAIC_CALL)
    if on_tpu() and n_calls != n_bare:
        raise AssertionError(f"{what}: {n_calls} Mosaic calls under Remat, "
                             f"{n_bare} bare")
    # the module's products round their operands to bf16; XLA may fuse the
    # rematerialised projections differently from the stored ones
    _close(got, want, BF16_TOL, what)
    log(f"  {what}: fwd+bwd match the bare module ({ran})")


def check_ssd_scan(t=8192, heads=64, head_dim=64, state=128, chunk=256,
                   n=1, kernel=True, groups=1) -> None:
    """The chunked state-space scan (ops/ssd.py, the Mamba-2 layers' path)
    against the recurrence one token at a time in float32, outputs and the
    gradient of every input, at granite-4.0-h-micro's widths (or, with
    ``groups`` 8, chunk 128 and n 2, Nemotron-3-Nano's: B and C in 8 B/C
    groups, head h reading group h // 8): decays from
    one to a thousand tokens, so the chunks' carried states matter. On the
    chip these shapes must take the Pallas kernels (ops/ssd_kernel.py;
    ``kernel``: what the scan's record has to say; off the chip every shape
    takes the XLA form)."""
    import math

    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops import ssd

    ks = jax.random.split(jax.random.PRNGKey(t), 4)
    x = _normal(1, (n, t, heads, head_dim), jnp.float32)
    a = -jnp.exp(jax.random.uniform(ks[0], (heads,), maxval=math.log(16.0)))
    rate = jnp.exp(jnp.linspace(math.log(1e-3), 0.0, heads))   # -dt a, a head
    dt = rate / -a * jnp.exp(0.3 * jax.random.normal(ks[1], (n, t, heads)))
    bc_shape = (n, t, state) if groups == 1 else (n, t, groups, state)
    b, c = (_normal(s, bc_shape, jnp.float32) for s in (2, 3))
    d = 1.0 + 0.1 * jax.random.normal(ks[2], (heads,))
    cot = _normal(4, x.shape, jnp.float32)
    args = (x, dt, a, b, c, d)
    what = (f"ssd_scan N={n} T={t} {heads}x{head_dim} state {state} in "
            f"{groups} B/C group(s) chunk {chunk}")
    ssd.take_scan_records()
    text, got = _fwd_bwd(lambda *v: ssd.ssd_scan(*v, chunk=chunk)[0], args, cot)
    record, = ssd.take_scan_records()
    if record["kernel"] != (kernel and on_tpu()):
        raise AssertionError(f"{what}: kernel={kernel} expected on "
                             f"{jax.default_backend()}, the record says {record}")
    if record["kernel"]:
        how = (f"{assert_mosaic(text, what)} Mosaic calls, "
               f"{record['heads_per_step']} heads a grid step")
    else:
        how = f"the XLA form, heads in groups of {record['head_group']}"
    _, want = _fwd_bwd(
        lambda *v: ssd.ssd_sequential(*v, segment=min(chunk, t)), args, cot)
    _close(got, want, BF16_TOL, what)
    log(f"  {what}: fwd + 6 input gradients match the sequential recurrence "
        f"({record['chunks']} chunks, {how})")


def check_relu2_tilings(rows=12288, hidden=2688, ffn=1856, held=8,
                        tiles=(128, 256, 384, 512, 640, 896, 1024),
                        reps=5) -> dict:
    """The two grouped products of ``relu2`` experts whose width no multiple
    of 128 divides (Nemotron-3-Nano's 2688 -> 1856 -> 2688 over the sized
    buffer's 12,288 rows, 8 groups), forward and backward, under each tile
    the grouped kernel may take for the ragged axis: each against
    ``jax.lax.ragged_dot`` in float32, then timed. ``nn/moe._tile`` takes its
    choice from this table (docs/performance.md). -> {tile: ms}."""
    import time

    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn import moe

    x = _normal(1, (rows, hidden), jnp.float32)
    w_up = 0.02 * _normal(2, (held, hidden, ffn), jnp.float32)
    w_down = 0.02 * _normal(3, (held, ffn, hidden), jnp.float32)
    cot = _normal(4, (rows, hidden), jnp.float32)
    # uneven groups that leave the buffer's last rows empty, as a step does
    sizes = jnp.asarray([(i + 1) * rows // (held * (held + 1) // 2) * 7 // 8
                         for i in range(held)], jnp.int32)
    live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]

    def layer(dot):
        # rows past the groups' total are unwritten memory from the kernel,
        # forward (the output) and backward (dx): selected away on both sides
        def fn(x, w_up, w_down):
            x = jnp.where(live, x, 0.0)
            h = jnp.square(jax.nn.relu(dot(x, w_up, sizes)))
            return jnp.where(live, dot(jnp.where(live, h, 0.0), w_down, sizes),
                             0.0)
        return fn

    _, want = _fwd_bwd(layer(lambda a, b, g: jax.lax.ragged_dot(
        a, b, g, precision=jax.lax.Precision.HIGHEST)), (x, w_up, w_down),
        jnp.where(live, cot, 0.0))
    rule, plain, table = moe._tile(ffn), moe._tile, {}
    for tile in tiles:
        moe._tile = lambda size, most=1024, t=tile: (
            t if size == ffn else plain(size, most))
        what = f"relu2 grouped products {rows}x{hidden}->{ffn}, tile {tile}"
        try:
            run = jax.jit(lambda *a: jax.vjp(layer(moe.grouped_dot), *a)[1](
                jnp.where(live, cot, 0.0)))
            got = (layer(moe.grouped_dot)(x, w_up, w_down),
                   run(x, w_up, w_down))
            _close(got, want, BF16_TOL, what)
            jax.block_until_ready(run(x, w_up, w_down))
            t0 = time.perf_counter()
            for _ in range(reps):
                out = run(x, w_up, w_down)
            jax.block_until_ready(out)
            table[tile] = (time.perf_counter() - t0) / reps * 1e3
            log(f"  {what}: matches ragged_dot, forward + backward "
                f"{table[tile]:.2f} ms{' (the rule)' if tile == rule else ''}")
        except Exception as e:  # noqa: BLE001 - a tile Mosaic refuses is a row
            table[tile] = None
            log(f"  {what}: refused: {str(e)[:200]}")
        finally:
            moe._tile = plain
    return table


def check_routed_experts(tokens=16384, hidden=2048, ffn=768, n_experts=256,
                         n_held=16, top_k=8) -> None:
    """One chip's share of a routed-experts layer (nn.RoutedExperts at
    JoyAI-LLM-Flash's sizes: 16 of 256 experts held, 8 a token) against the
    same layer computed densely, every held expert over every token with a
    mask: outputs and the gradients of the input, the router and the three
    expert tensors. Twice: with a router that spreads the tokens, so the
    local pairs fit the sized buffer (``moe.buffer_rows``: one block), and
    with every token's first choice a held expert, so they overflow it and
    the layer goes over the sort block by block. The path taken is logged."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.nn import moe
    from bigdl_tpu.utils import precision

    layer = nn.RoutedExperts(n_experts, ffn, top_k,
                             experts_held=tuple(range(n_held)))
    x = _normal(tokens, (tokens, hidden), jnp.float32)
    layer.build(jax.random.PRNGKey(tokens), jax.ShapeDtypeStruct(x.shape, x.dtype))
    spread = layer.get_parameters()
    spread = {**spread, **{k: 4.0 * spread[k]       # outputs of order one
                           for k in ("w_gate", "w_up", "w_down")}}
    # the held expert 0 ahead of every other for every token
    crowded = {**spread, "router": spread["router"].at[:, 0].set(
        jnp.sign(x[0]) * 0.02)}
    crowded_x = jnp.abs(x) * jnp.sign(x[0])
    held = jnp.arange(n_held)
    rows = moe.buffer_rows(tokens * top_k, n_held, n_experts)
    if rows > tokens:   # the crowded router sends a pair a token to expert 0
        raise ValueError(f"a share of {n_held}/{n_experts} has no sized buffer "
                         f"to overflow: {rows} rows for {tokens} tokens")
    cot = _normal(tokens + 1, x.shape, jnp.float32)

    def dense(params, x):
        top_p, top_e = moe.route_top_k(x, params["router"], top_k)

        def one_expert(out, expert):
            e, w_gate, w_up, w_down = expert
            w = jnp.sum(jnp.where(top_e == e, top_p, 0.0), axis=-1)
            h = jax.nn.silu(precision.dot_acc32(x, w_gate)) \
                * precision.dot_acc32(x, w_up)
            return out + w[:, None] * precision.dot_acc32(h, w_down), None

        return jax.lax.scan(one_expert, jnp.zeros_like(x), (
            held, params["w_gate"], params["w_up"], params["w_down"]))[0]

    for name, params, x in (("spread", spread, x), ("crowded", crowded, crowded_x)):
        what = (f"routed experts {name} T={tokens} {n_held}/{n_experts} held "
                f"top-{top_k}, buffer {rows} of {tokens * top_k} rows")
        counters = jax.jit(lambda p, x: layer.apply(p, layer.get_state(), x)[1][
            "_counters"])(params, x)
        local, over, dropped = (int(counters[k]) for k in (
            "moe_pairs_local", "moe_overflow_layers", "moe_dropped_pairs"))
        if dropped or over != (local > rows) or over != (name == "crowded"):
            raise AssertionError(f"{what}: {local} local pairs, overflow "
                                 f"{over}, dropped {dropped}")
        _, got = _fwd_bwd(lambda p, x: layer.apply(p, layer.get_state(), x)[0],
                          (params, x), cot)
        _close(got, _fwd_bwd(dense, (params, x), cot)[1], BF16_TOL, what)
        log(f"  {what}: {local} local pairs in {-(-local // rows)} block(s), "
            f"fwd + 5 gradients match the dense layer")


def check_fused(rows: int, hidden: int, conv_shape) -> None:
    """Engine.set_fused_kernels(True) against the unfused path of the same
    public call sites: nn.LayerNormalization (whose unfused chain is
    layer_norm_reference), nn.RMSNorm, and the Linear / conv bias+activation
    epilogues (precision.bias_act / channel_bias_act)."""
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.utils import precision
    from bigdl_tpu.utils.engine import Engine

    gain = 1.0 + 0.1 * _normal(1, (hidden,), jnp.float32)
    bias = 0.1 * _normal(2, (hidden,), jnp.float32)
    cbias = jnp.clip(0.1 * _normal(3, (conv_shape[1],), jnp.float32), -.1, .1)
    ln, rms = nn.LayerNormalization(hidden), nn.RMSNorm(hidden)
    cases = []
    for dtype, tol in ((jnp.float32, F32_TOL), (jnp.bfloat16, BF16_TOL)):
        x = _normal(4, (rows, hidden), dtype)
        cot = _normal(5, (rows, hidden), jnp.float32)
        # ReLU's gate is a step: keep |x + b| away from 0, or the kernel's
        # f32 sum and the unfused bf16 sum legitimately disagree on the sign
        g = _normal(6, conv_shape, jnp.float32)
        xc = (jnp.sign(g) * (0.25 + jnp.abs(g))).astype(dtype)
        ccot = _normal(7, conv_shape, jnp.float32)
        tag = jnp.dtype(dtype).name
        cases += [
            (f"fused LayerNorm {rows}x{hidden} {tag}", tol, (x, gain, bias),
             cot, lambda x, g, b: ln.apply(
                 {"weight": g, "bias": b}, {}, x, training=True, rng=None)[0]),
            (f"fused RMSNorm {rows}x{hidden} {tag}", tol, (x, gain), cot,
             lambda x, g: rms.apply(
                 {"weight": g}, {}, x, training=True, rng=None)[0]),
            (f"fused bias+gelu {rows}x{hidden} {tag}", tol, (x, bias), cot,
             lambda x, b: precision.bias_act(x, b, "gelu")),
            (f"fused conv bias+relu {'x'.join(map(str, conv_shape))} {tag}",
             tol, (xc, cbias), ccot,
             lambda x, b: precision.channel_bias_act(x, b, "relu")),
        ]
    Engine.set_fused_kernels(True)  # read at trace time
    try:
        fused = [_fwd_bwd(fn, args, cot) for _, _, args, cot, fn in cases]
    finally:
        Engine.set_fused_kernels(False)
    for (what, tol, args, cot, fn), (text, got) in zip(cases, fused):
        n_calls = assert_mosaic(text, what)
        _close(got, _fwd_bwd(fn, args, cot)[1], tol, what)
        log(f"  {what}: fwd+bwd match the unfused path "
            f"({n_calls} Mosaic calls)")


def check_maxpool(shape=(128, 64, 112, 112)) -> None:
    """The Pallas max-pool backward (BIGDL_MAXPOOL_GRAD_IMPL=pallas) at the
    ResNet-50 stem pool, 3x3 stride 2 pad 1 — the geometry that ran out of
    VMEM under the round-5 libtpu — against XLA's SelectAndScatter."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn

    pool = nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1)

    def fn(x):
        return pool.apply({}, {}, x, training=True, rng=None)[0]

    x = _normal(8, shape, jnp.float32)
    cot = _normal(9, jax.eval_shape(fn, x).shape, jnp.float32)
    what = f"max-pool backward {'x'.join(map(str, shape))} 3x3/s2 f32"
    os.environ["BIGDL_MAXPOOL_GRAD_IMPL"] = "pallas"  # read at trace time
    try:
        text, got = _fwd_bwd(fn, (x,), cot)
    finally:
        del os.environ["BIGDL_MAXPOOL_GRAD_IMPL"]
    n_calls = assert_mosaic(text, what)
    _close(got, _fwd_bwd(fn, (x,), cot)[1], F32_TOL, what)
    log(f"  {what}: matches SelectAndScatter ({n_calls} Mosaic calls)")


def check_transformer_step(t=2048, batch=8, vocab=8192, hidden=512, heads=8,
                           ffn=2048, layers=2, iters=4) -> None:
    """Flash attention where users meet it: nn.Transformer(mode="lm") through
    LocalOptimizer, impl="auto" choosing the kernel from the shapes."""
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.obs import Telemetry
    from bigdl_tpu.optim import SGD, LocalOptimizer, Trigger
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(2)
    ids = np.random.default_rng(2).integers(
        1, vocab, (2 * batch, t)).astype(np.int32)
    lm = nn.Transformer(
        vocab_size=vocab, hidden_size=hidden, num_heads=heads,
        filter_size=ffn, num_hidden_layers=layers, postprocess_dropout=0.0,
        attention_dropout=0.0, relu_dropout=0.0, mode="lm")
    opt = LocalOptimizer(
        lm, DataSet.array(ids, np.roll(ids, -1, axis=1).astype(np.int64),
                          batch_size=batch),
        nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(),
                                    size_average=True))
    opt.set_optim_method(SGD(learningrate=0.05, momentum=0.9))
    opt.set_end_when(Trigger.max_iteration(iters))
    tel = Telemetry()
    opt.set_telemetry(tel)
    opt.optimize()
    losses = _check_losses(tel, iters, "phase D transformer")
    step, specs = opt._step_export_info
    n_calls = assert_mosaic(step.lower(*specs).as_text(),
                            "nn.Transformer LocalOptimizer step")
    tiles = [tile for r in tel.ring.records if r.get("type") == "compile"
             for tile in r.get("flash_tiles", ())]
    tel.close()
    if on_tpu() and len(tiles) != 1:  # one attention shape; the CPU is dense
        raise AssertionError(
            f"phase D transformer: want one flash tile choice in the compile "
            f"record, got {tiles}")
    log(f"  nn.Transformer lm T={t} d={hidden // heads} x{layers} layers "
        f"through LocalOptimizer: loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
        f"{n_calls} Mosaic calls in the step, compile record's flash_tiles "
        f"{tiles}")


def phase_kernels() -> None:
    check_flash(t=1024, d=64)
    check_flash(t=4096, d=128)
    # the cells' shapes, a quarter of their heads: Mellum2's full and
    # sliding layers (groups of 8), granite's (head size 64, groups of 4),
    # JoyAI's (q/k 192, v 128)
    check_flash(t=8192, d=128, n=1, h=8, hkv=1, lengths=False)
    check_flash(t=8192, d=128, n=1, h=8, hkv=1, window=1024, lengths=False)
    check_flash(t=8192, d=64, n=1, h=8, hkv=2, lengths=False)
    check_flash_latent()
    check_flash_remat(t=2048, d=128)
    check_ssd_scan()
    # a chunk of 192 is no multiple of 128: this one must fall back
    check_ssd_scan(t=1536, heads=8, chunk=192, kernel=False)
    # Nemotron-3-Nano's scan: 8 B/C groups, a group's 8 heads a grid step
    check_ssd_scan(chunk=128, n=2, groups=8)
    check_routed_experts()
    check_relu2_tilings(tiles=(512, 640))
    # hidden 2048 (the LM widths the roadmap names) and ResNet-50's
    # res2 conv epilogue (b128: 128x256x56x56)
    check_fused(rows=4096, hidden=2048, conv_shape=(128, 256, 56, 56))
    check_maxpool()
    check_transformer_step()
    log("phase D kernels: ok")


# --------------------------------------------------------------- E: serving
def phase_serving(model, image=224, batch=8, requests=32, clients=4) -> None:
    """ModelServer over the trained phase-B model: concurrent single-record
    requests must equal the serial Predictor rows bit for bit (the same
    executable), with no compile after warm-up."""
    from bigdl_tpu.optim.predictor import Predictor
    from bigdl_tpu.serving import ModelServer

    records, _ = _image_set(requests, image, 1, seed=3)
    want = np.asarray(Predictor(model, batch_size=batch).predict(records))
    got = [None] * requests
    with ModelServer() as srv:
        srv.register("resnet50", model, sample_input=records[0],
                     batch_size=batch, max_delay_ms=5.0)
        info = srv.models()["resnet50"]
        warm = srv.telemetry.compile_count

        def client(k: int) -> None:
            for i in range(k, requests, clients):
                got[i] = srv.infer("resnet50", records[i]).result(timeout=120)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        after = srv.telemetry.compile_count
    if any(g is None for g in got):
        raise AssertionError("phase E: a client thread died before its result")
    got = np.stack(got)
    if not (np.all(np.isfinite(got)) and got.shape == want.shape):
        raise AssertionError(f"phase E: bad outputs {got.shape} vs {want.shape}")
    if not np.array_equal(got, want):
        raise AssertionError(
            f"phase E: served rows differ from the serial Predictor "
            f"(max abs diff {np.max(np.abs(got - want)):.3g})")
    if after != warm:
        raise AssertionError(
            f"phase E: {after - warm} compiles after warm-up")
    log(f"phase E serving: ok ({requests} requests from {clients} threads "
        f"bit-equal to the serial Predictor, warm-up {info['warmup_s']:.1f} s "
        f"/ {info['warmup_compiles']} compile(s), 0 compiles after)")


# ------------------------------------------------------------- F: readback
def _readback(opt, ds, base, batch, epochs, hold, step_s, what) -> float:
    """Drive ``opt._prefetch_batches`` over ``ds`` as the epoch loop does, a
    pull every ``step_s`` seconds, keep the last ``hold`` device batches, and
    compare each, as it leaves that window, bitwise with the rows the epoch's
    order names. By then its host buffer has been handed back and gathered
    into again, so a buffer reused under a running copy, or under a device
    array that lives in it, shows as another batch's rows. Returns the share
    of batches gathered into a handed-back buffer."""
    x, y = base.features, base.labels
    reused, held, compared = [], collections.deque(), 0

    def noted(stream):
        for b in stream:
            reused.append(b.host_lease.reused)
            yield b

    def check(dev, rows, at):
        got_x, got_y = np.asarray(dev.get_input()), np.asarray(dev.get_target())
        if not (np.array_equal(got_x.view(np.uint32), x[rows].view(np.uint32))
                and np.array_equal(got_y, y[rows])):
            wrong = np.flatnonzero((got_x != x[rows]).any(axis=(1, 2, 3)))
            raise AssertionError(
                f"{what}: device batch {at} is not the rows its epoch's "
                f"order names ({len(wrong)} of {len(rows)} rows differ, "
                f"first {wrong[:4]})")

    def check_down_to(n):
        nonlocal compared
        while len(held) > n:
            check(*held.popleft())
            compared += 1

    for epoch in range(1, epochs + 1):
        ds.shuffle(epoch)
        order = base._order.copy()
        batches = opt._prefetch_batches(noted(ds.data(train=True)))
        for i, dev in enumerate(batches):
            time.sleep(step_s)  # the device step the worker runs ahead of
            held.append((dev, order[i * batch:(i + 1) * batch], (epoch, i)))
            check_down_to(hold)
    check_down_to(0)
    base._host_buffers.clear()  # the run is over
    if compared != len(reused) or compared != epochs * (len(x) // batch):
        raise AssertionError(f"{what}: compared {compared} batches of "
                             f"{len(reused)} gathered")
    share = sum(reused) / len(reused)
    if on_tpu() and share < 0.5:
        raise AssertionError(
            f"{what}: only {sum(reused)} of {len(reused)} batches were "
            f"gathered into a handed-back buffer: recycling is not engaged")
    return share


def phase_readback(image=224, batch_per_chip=256, batches=8, epochs=3,
                   hold=3, step_s=0.05):
    """Recycled host batch buffers never change what reaches the device.
    Distinct float32 records (one random image plus the record's number)
    through the two placement seams the trainers use."""
    import jax

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.optim import SGD, LocalOptimizer, Trigger
    from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    def records(batch):
        n = batch * batches
        one = np.random.default_rng(0).standard_normal(
            (3, image, image)).astype(np.float32)
        x = np.empty((n, 3, image, image), np.float32)
        np.add(one[None], np.arange(n, dtype=np.float32)[:, None, None, None],
               out=x)
        return DataSet.array(x, np.arange(n) % 8, batch_size=batch)

    def model():
        return nn.Sequential(nn.Flatten(), nn.Linear(3 * image * image, 8),
                             nn.LogSoftMax())

    RandomGenerator.set_seed(1)
    base = records(batch_per_chip)
    opt = LocalOptimizer(model(), base, nn.ClassNLLCriterion())
    one = _readback(opt, base, base, batch_per_chip, epochs, hold, step_s,
                    "phase F, device_put")

    devices = jax.local_devices()
    n = len(devices)
    Engine.init(devices=devices)
    batch = batch_per_chip * n
    base = records(batch)
    ds = DataSet.distributed(base, n)
    opt = DistriOptimizer(model(), ds, nn.ClassNLLCriterion(),
                          parameter_sync="sharded")
    opt.set_optim_method(SGD(learningrate=0.01))
    opt.set_end_when(Trigger.max_iteration(2))
    opt.optimize()  # two steps of a small model: builds the mesh's place_pair
    if opt._place_batch is None or len(base._host_buffers) != 0:
        raise AssertionError(
            f"phase F: after optimize() place_pair is {opt._place_batch} and "
            f"the dataset holds {len(base._host_buffers)} free buffers")
    many = _readback(opt, ds, base, batch, epochs, hold, step_s * 3,
                     "phase F, place_pair")
    total = epochs * batches
    log(f"phase F readback: ok ({total} batches of {batch_per_chip}x3x{image}"
        f"x{image} f32 bitwise equal after {hold} later pulls on 1 device, "
        f"host_buf_reused {one:.3f}; {total} batches of {batch} through "
        f"place_pair on {n} device(s), host_buf_reused {many:.3f})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--expect-cache-hit", action="store_true",
                    help="second run on one cache dir: fail unless the "
                         "ResNet-50 train step compiled nothing fresh")
    args = ap.parse_args()

    device = phase_device()
    build_native()
    model, compile_rec = phase_train(expect_cache_hit=args.expect_cache_hit)
    phase_distri()
    phase_kernels()
    phase_serving(model)
    phase_readback()
    os.makedirs(os.path.dirname(LAST_RUN), exist_ok=True)
    with open(LAST_RUN, "w") as f:
        json.dump(compile_rec, f)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
