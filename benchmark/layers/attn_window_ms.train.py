"""Device time a step of the flash kernels' ops in the sliding-window layers
(scope ``attn_window``: forward, and in the backward pass the recomputed
forward and the two backward kernels). From shapes it should be 3 x 23 % =
0.70 of ``attn_full_ms.train`` at 8192 tokens and a window of 1024; far above
that, the kernel is visiting tiles it masks."""

from benchmark.lib import scopes

NAME = "attn_window_ms.train"
UNIT = "ms"
LAYER = "kernels"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scopes.scope_ms(run, "attn_window")
