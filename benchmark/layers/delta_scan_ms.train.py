"""Device time a step in the scope ``delta_scan``: the Gated DeltaNet layers'
gated delta rule from the L2 norms of q and k, beta and the log decay to the
output o (the chunks' matrices, the triangular solve, the carried states,
the outputs), forward and backward, recomputation included. A program
without the scope gives nothing."""

from benchmark.lib import scope_times

NAME = "delta_scan_ms.train"
UNIT = "ms"
LAYER = "kernels"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"
DELTA_SCOPES = ("delta_proj", "delta_conv", "delta_scan")


def read(run):
    return scope_times.scope_ms(run, "delta_scan", DELTA_SCOPES)
