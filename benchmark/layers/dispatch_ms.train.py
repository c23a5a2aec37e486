"""Host time of one dispatch of the jitted step, as the drive loop times it."""

from benchmark.lib import stats

NAME = "dispatch_ms.train"
UNIT = "ms"
LAYER = "optimizer drive loop"
MOVES = "train_records_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    ds = [r["dispatch_s"] for r in run.steps if r.get("dispatch_s") is not None]
    return stats.median(ds) * 1e3 if ds else None
