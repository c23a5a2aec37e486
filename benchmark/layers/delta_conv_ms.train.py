"""Device time a step in the scope ``delta_conv``: the Gated DeltaNet layers'
causal depthwise conv over q, k and v and its SiLU, forward and backward,
recomputation included. A program without the scope gives nothing."""

from benchmark.lib import scope_times

NAME = "delta_conv_ms.train"
UNIT = "ms"
LAYER = "linear-attention layer"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"
DELTA_SCOPES = ("delta_proj", "delta_conv", "delta_scan")


def read(run):
    return scope_times.scope_ms(run, "delta_conv", DELTA_SCOPES)
