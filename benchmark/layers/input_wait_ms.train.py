"""The prefetch worker's wait on ``next(src)`` for a step's batch: the
dataset's gather, as the worker sees it."""

from benchmark.lib import stats

NAME = "input_wait_ms.train"
UNIT = "ms"
LAYER = "dataset + _prefetch"
MOVES = "train_records_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    waits = [r["input_wait_s"] for r in run.steps
             if r.get("input_wait_s") is not None]
    return stats.median(waits) * 1e3 if waits else None
