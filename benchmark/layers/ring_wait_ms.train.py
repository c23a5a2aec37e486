"""Time a step waited for data: the driver thread's ``ring_wait`` span around
``ring.get()`` in ``_prefetch_batches``, as the step records carry it. Zero
while the prefetch worker stays ahead of the device."""

from benchmark.lib import spans, stats

NAME = "ring_wait_ms.train"
UNIT = "ms"
LAYER = "dataset + _prefetch"
MOVES = "train_records_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    waits = spans.seconds(run.steps, "ring_wait")
    return stats.median(waits) * 1e3 if waits else None
