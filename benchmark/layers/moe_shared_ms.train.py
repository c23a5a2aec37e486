"""Device time a step in the scope ``moe_shared``: the shared expert of every
routed layer (2048 -> 2 x 768 -> 2048 over every token), forward and
backward, recomputation included."""

from benchmark.lib import scope_names, scope_times

NAME = "moe_shared_ms.train"
UNIT = "ms"
LAYER = "experts layer"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scope_times.scope_ms(run, "moe_shared", scope_names.LATENT_SCOPES)
