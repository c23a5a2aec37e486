"""Host time inside the jitted call itself (``dispatch/step_call``): pjit's
dispatch over the state's leaves, which blocks when the runtime's queue is
full. Median over the window's steps."""

from benchmark.lib import spans, stats

NAME = "step_call_ms.train"
UNIT = "ms"
LAYER = "jitted train step"
MOVES = "train_records_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    calls = spans.seconds(run.steps, "dispatch/step_call")
    return stats.median(calls) * 1e3 if calls else None
