"""Driver ``train_ref``: driver ``train``'s run unchanged (the window, the
metrics and the loss rules are that file's), and then, after the window and
outside ``setup_s``, the configuration's comparison with its float32
reference on one batch of the mix at the timed sizes. Its verdict is ANDed
into ``correct``; every compared number is logged beside its limit on the
line before the result.

Driver ``train`` hands the per-layer readers the window's steady steps only;
a reader that sets a counter against the TRACED steps' device time needs those
steps' own records, so an exporter of this driver's keeps every step record
and ``run.traced_steps`` holds the traced ones."""

from __future__ import annotations

import gc
import time
from types import SimpleNamespace

from benchmark.drivers import train

END_TO_END = train.END_TO_END


class _EveryStep:
    """Telemetry exporter: keeps every step record."""

    def __init__(self):
        self.steps = []

    def emit(self, record) -> None:
        if record.get("type") == "step":
            self.steps.append(record)

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


def _kept_by(config_module, keeper):
    """``config_module`` as driver ``train`` uses it, with ``keeper`` among
    the exporters of whatever telemetry the optimizer is given."""
    def build(cfg, traffic, seed, chips):
        built = config_module.build(cfg, traffic, seed, chips)
        keeper.steps_per_epoch = traffic.steps_per_epoch
        opt = built["optimizer"]
        attach = opt.set_telemetry

        def set_telemetry(tel):
            tel.exporters.append(keeper)
            return attach(tel)

        opt.set_telemetry = set_telemetry
        return built

    return SimpleNamespace(build=build)


def run(cell: dict, cfg: dict, config_module, mix: dict, generator, *,
        seed: int, log, **kw):
    keeper = _EveryStep()
    out = train.run(cell, cfg, _kept_by(config_module, keeper), mix, generator,
                    seed=seed, log=log, **kw)
    out.traced_steps = []
    if out.trace_dir is not None:
        n = int(mix["traced_steps"])
        start, _ = train._traced_range(1, int(mix["warm_steps"]), n,
                                       keeper.steps_per_epoch)
        out.traced_steps = [r for r in keeper.steps
                            if start <= r["iteration"] < start + n]
    # the optimizer, its model and 7 GB of state went out of scope with
    # train.run; the cycles of a traced step are what is left to collect
    gc.collect()
    import jax

    counters = sorted({k for r in out.steps for k in r if k.startswith("moe_")})
    log(live_device_bytes_before_comparison=sum(
            a.nbytes for a in jax.live_arrays()),
        window_mean_of_counters={
            k: sum(r[k] for r in out.steps) / len(out.steps) for k in counters},
        window_max_of_counters={
            k: max(r[k] for r in out.steps) for k in counters})
    t = time.perf_counter()
    agrees = config_module.compare(cfg, mix, generator, seed, log)
    log(reference_comparison_s=time.perf_counter() - t, agrees=agrees)
    out.correct = bool(out.correct and agrees)
    return out
