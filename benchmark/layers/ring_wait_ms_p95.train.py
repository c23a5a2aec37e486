"""The tail of the driver's wait for data: 95th percentile (nearest rank) over
the window's steps of the ``ring_wait`` span. A step that crosses an epoch
boundary waits on a cold ring, so this is where the boundary shows."""

from benchmark.lib import spans, stats

NAME = "ring_wait_ms_p95.train"
UNIT = "ms"
LAYER = "dataset + _prefetch"
MOVES = "train_step_ms_p95"
SOURCE = "program_span"


def read(run):
    waits = spans.seconds(run.steps, "ring_wait")
    return stats.percentile(waits, 95) * 1e3 if waits else None
