"""Device time a step of the flash kernels' ops in the full-attention layers
(scope ``attn_full``), forward and backward, recomputation included."""

from benchmark.lib import scopes

NAME = "attn_full_ms.train"
UNIT = "ms"
LAYER = "kernels"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scopes.scope_ms(run, "attn_full")
