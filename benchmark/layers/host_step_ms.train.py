"""The drive loop's own time: a step's wall less the driver's wait for data
(``ring_wait``) and its block on the device (``loss_pull``), median over the
window. What the step would cost with an instant device and instant input.
A step's wall is the time between the benchmark's stamps of two step records,
which is the interval the second record's spans were drained over."""

from benchmark.lib import stats

NAME = "host_step_ms.train"
UNIT = "ms"
LAYER = "optimizer drive loop"
MOVES = "train_records_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    own = []
    for wall, r in zip(run.walls, run.steps):
        spans = r.get("spans") or {}
        if "ring_wait" in spans and "loss_pull" in spans:
            own.append(wall - spans["ring_wait"]["s"] - spans["loss_pull"]["s"])
    return stats.median(own) * 1e3 if own else None
