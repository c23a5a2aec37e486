"""Device time a step in the scope ``ssm_proj``: the state-space layers' two
projections (2048 -> 8512, 4096 -> 2048) and the gated RMSNorm between scan
and output, forward and backward, recomputation included."""

from benchmark.lib import scope_times

NAME = "ssm_proj_ms.train"
UNIT = "ms"
LAYER = "state-space layer"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scope_times.scope_ms(run, "ssm_proj")
