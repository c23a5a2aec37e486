"""Device time a step in the scope ``ssm_scan``: the state-space layers'
chunked scan from the step size to ``y`` (running sums, decays, the four
products, the chunks' carried states, the ``D`` term), forward and backward,
recomputation included."""

from benchmark.lib import scope_times

NAME = "ssm_scan_ms.train"
UNIT = "ms"
LAYER = "kernels"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scope_times.scope_ms(run, "ssm_scan")
