"""The per-step walk of the module tree after the call
(``dispatch/model_sync``: ``model.set_parameters`` + ``model.set_state``).
Median over the window's steps."""

from benchmark.lib import spans, stats

NAME = "model_sync_ms.train"
UNIT = "ms"
LAYER = "optimizer drive loop"
MOVES = "train_records_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    syncs = spans.seconds(run.steps, "dispatch/model_sync")
    return stats.median(syncs) * 1e3 if syncs else None
