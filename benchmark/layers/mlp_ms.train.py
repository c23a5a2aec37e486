"""Device time a step in the scope ``mlp``: every layer's dense gated MLP
(two products and silu(a) * b), forward and backward, recomputation
included."""

from benchmark.lib import scope_times

NAME = "mlp_ms.train"
UNIT = "ms"
LAYER = "dense MLP"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scope_times.scope_ms(run, "mlp")
