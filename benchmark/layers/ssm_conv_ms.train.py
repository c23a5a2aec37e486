"""Device time a step in the scope ``ssm_conv``: the state-space layers'
causal depthwise conv of 4 with its bias and silu, forward and backward,
recomputation included."""

from benchmark.lib import scope_times

NAME = "ssm_conv_ms.train"
UNIT = "ms"
LAYER = "state-space layer"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scope_times.scope_ms(run, "ssm_conv")
