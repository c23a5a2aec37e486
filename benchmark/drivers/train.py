"""Driver ``train``: one cell's configuration through ``optimize()``.

The trainer is the system under test; everything that measures is here. An
exporter of the benchmark's own on ``Telemetry(exporters=[...])`` stamps every
step record with the benchmark's clock as it is emitted (the flush that ends
in the one-step-late ``float(loss)``, a real sync). The window opens at the
stamp of the step that ends warm-up and closes at the stamp of the last step:
the end trigger fires at the first dispatch after ``seconds`` and the loop
then drains that step, so the window holds whole steps and lasts ``seconds``
plus at most two steps. Rates are all its records over all its time.
"""

from __future__ import annotations

import math
import os
import shutil
import time
from types import SimpleNamespace

from benchmark.lib import hostlib, stats

END_TO_END = {
    "setup_s": "s",
    "train_records_per_s_per_chip": "records/s/chip",
    "train_step_ms_p95": "ms",
}


class _Stamper:
    """Telemetry exporter: keeps every record, stamps the step records."""

    def __init__(self, warm_steps: int):
        self.warm_steps = warm_steps
        self.stamps, self.steps, self.others = [], [], []
        self.t_open = None

    def emit(self, record) -> None:
        if record.get("type") != "step":
            self.others.append(record)
            return
        now = time.perf_counter()
        self.stamps.append(now)
        self.steps.append(record)
        if len(self.steps) == self.warm_steps + 1:  # the compile, then warm-up
            self.t_open = now

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


def _traced_range(first: int, warm: int, length: int, per_epoch: int):
    """Step numbers (1-based, as dispatched) of the traced steps: the first
    run of ``length`` steps inside the window that keeps three steps clear of
    an epoch boundary, so the trace shows the steady step."""
    k = warm + 6
    if per_epoch >= length + 6:
        while not 3 <= (k - 1) % per_epoch <= per_epoch - length - 3:
            k += 1
    return first + k - 1, k


def _program_temp_bytes(opt) -> int:
    """Temporaries of the compiled train step, from its ``memory_analysis``.
    On this TPU runtime the allocator's ``peak_bytes_in_use`` counts live
    buffers (parameters, optimizer state, staged batches) but not the
    running program's temporaries, which are most of what a training step
    holds (PR 24: 1.35 GB reported against 9.2 GB of temporaries, ResNet-50
    b256). The step is lowered again from the geometry its first dispatch
    recorded; the persistent cache serves the executable."""
    info = getattr(opt, "_step_export_info", None)
    if info is None:
        return 0
    step, specs = info
    analysis = step.lower(*specs).compile().memory_analysis()
    return int(getattr(analysis, "temp_size_in_bytes", 0) or 0)


def _check(cfg: dict, losses, window_losses, compiles, window_compiles):
    """The comparison that decides ``correct``; -> list of broken rules."""
    rule, broken = cfg["correct"], []
    if not all(math.isfinite(v) for v in losses):
        broken.append("a loss is not finite")
    if compiles != 1 or window_compiles != 0:
        broken.append(f"{compiles} compiles of the train step in the run, "
                      f"{window_compiles} inside the window; want 1 and 0")
    if abs(losses[0] - rule["first_loss"]) > rule["first_loss_tolerance"]:
        broken.append(f"first loss {losses[0]:.4f} is not within "
                      f"{rule['first_loss_tolerance']} of {rule['first_loss']}")
    first = stats.median(losses[:rule["first_losses"]])
    last = stats.median(window_losses[-rule["last_losses"]:])
    if not last < first:
        broken.append(f"loss did not fall: median of the last "
                      f"{rule['last_losses']} steps {last:.4f}, of the first "
                      f"{rule['first_losses']} {first:.4f}")
    return broken


def run(cell: dict, cfg: dict, config_module, mix: dict, generator, *,
        seed: int, seconds: float, trace: bool, t0: float, chips: int,
        scratch: str, on_tpu: bool, log) -> SimpleNamespace:
    import jax

    from bigdl_tpu.obs import Telemetry
    from bigdl_tpu.utils.engine import Engine

    marks = {"imports": time.perf_counter() - t0}
    log(native=hostlib.ensure(required=on_tpu),
        compile_cache=Engine.ensure_compilation_cache())
    traffic = generator.make(mix, cfg, seed, chips)
    marks["records_made"] = time.perf_counter() - t0
    built = config_module.build(cfg, traffic, seed, chips)
    marks["optimizer_built"] = time.perf_counter() - t0
    opt = built["optimizer"]

    warm = int(mix["warm_steps"])
    stamper = _Stamper(warm)
    tel = Telemetry(exporters=[stamper])
    opt.set_telemetry(tel)
    opt.set_end_when(
        lambda state: stamper.t_open is not None
        and time.perf_counter() - stamper.t_open >= seconds)
    trace_dir = traced = None
    if trace:
        trace_dir = os.path.join(scratch, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        n = int(mix["traced_steps"])
        start, k = _traced_range(int(opt.optim_method.state.get("neval", 1)),
                                 warm, n, traffic.steps_per_epoch)
        opt.set_profile(trace_dir, start_iteration=start, num_iterations=n)
        # starting and stopping the profiler stalls the loop: the steps next
        # to the traced ones are no sample of the steady step either
        traced = range(k - 1, k + n + 2)
    opt.optimize()
    tel.close()

    stamps, steps = stamper.stamps, stamper.steps
    if stamper.t_open is None or len(steps) <= warm + 1:
        raise SystemExit(f"benchmark: the run ended after {len(steps)} steps, "
                         f"before the window opened")
    walls = [b - a for a, b in zip(stamps[warm:], stamps[warm + 1:])]
    window = steps[warm + 1:]
    window_s = stamps[-1] - stamper.t_open
    records = sum(r["records"] for r in window)
    losses = [r["loss"] for r in steps]
    compiles = [r for r in stamper.others if r.get("type") == "compile"]
    broken = _check(
        cfg, losses, losses[warm + 1:], sum(r["count"] for r in compiles),
        window[-1]["compile_count"] - steps[warm]["compile_count"])
    epochs = [r["epoch"] for r in window]
    marks["first_step_flushed"] = stamps[0] - t0
    marks["window_open"] = stamper.t_open - t0
    log(setup_marks_s=marks)  # seconds since the first line of run.py
    log(window_s=window_s, steps=len(window), batch=traffic.batch,
        epoch_boundaries=sum(a != b for a, b in zip(epochs, epochs[1:])),
        step_ms_median=stats.median(walls) * 1e3,
        step_ms_p90_p95_p98_p99_max=[
            stats.percentile(walls, q) * 1e3 for q in (90, 95, 98, 99, 100)],
        first_losses=losses[:8], last_losses=losses[-8:], broken=broken)
    log(walls_ms=[round(w * 1e3, 2) for w in walls])

    # a traced run's steady steps: those away from the profiler's start/stop
    keep = [i for i in range(len(window))
            if traced is None or (warm + 2 + i) not in traced]
    failed = sum(not math.isfinite(r["loss"]) for r in window)
    mem = [d.memory_stats() or {} for d in jax.local_devices()]
    live_peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
    temp = _program_temp_bytes(opt)
    log(allocator_peak_bytes=live_peak, program_temp_bytes=temp,
        memory_stats=mem[0])
    return SimpleNamespace(
        correct=not broken, attempted=len(window), failed=failed,
        end_to_end={
            "setup_s": stamper.t_open - t0,
            "train_records_per_s_per_chip": records / window_s / chips,
            "train_step_ms_p95": stats.percentile(walls, 95) * 1e3,
        },
        units=END_TO_END,
        memory_peak_bytes=live_peak + temp,
        # for the per-layer readers
        cfg=cfg, chips=chips, batch=traffic.batch,
        steps=[window[i] for i in keep], walls=[walls[i] for i in keep],
        records=stamper.others, forward=built["forward"],
        trace_dir=trace_dir,
    )
