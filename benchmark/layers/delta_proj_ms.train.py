"""Device time a step in the scope ``delta_proj``: the Gated DeltaNet layers'
six in-projections (q, k, v, the gate z, the decay's a and beta's b), the
output norm with its gate and the out-projection, forward and backward,
recomputation included. A program without the scope gives nothing."""

from benchmark.lib import scope_times

NAME = "delta_proj_ms.train"
UNIT = "ms"
LAYER = "linear-attention layer"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"
DELTA_SCOPES = ("delta_proj", "delta_conv", "delta_scan")


def read(run):
    return scope_times.scope_ms(run, "delta_proj", DELTA_SCOPES)
