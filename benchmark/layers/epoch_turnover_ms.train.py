"""The epoch boundary's own cost, apart from the cold ring: mean of the
``epoch_turnover`` span (the prefetch generator's teardown, the last step's
flush, end-of-epoch triggers, shuffle, ``data()``, the new prefetch thread)
over the boundaries that fell in the window. ``None`` when none did."""

from benchmark.lib import spans

NAME = "epoch_turnover_ms.train"
UNIT = "ms"
LAYER = "optimizer drive loop"
MOVES = "train_step_ms_p95"
SOURCE = "program_span"


def read(run):
    turns = spans.aggregates(run.steps, "epoch_turnover")
    n = sum(a["n"] for a in turns)
    return sum(a["s"] for a in turns) / n * 1e3 if n else None
