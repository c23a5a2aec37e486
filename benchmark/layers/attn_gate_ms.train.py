"""Device time a step in the scope ``attn_gate``: the per-head output gate of
the gated attention layers (the projection x W_g to one logit a head and
token, its sigmoid, and each head's output times it, between the attention
kernel and the output projection), forward and backward, recomputation
included. A program without the scope gives nothing."""

from benchmark.lib import scope_times

NAME = "attn_gate_ms.train"
UNIT = "ms"
LAYER = "attention layer"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scope_times.scope_ms(run, "attn_gate", ("attn_gate",))
