"""Device time a step in the scope ``moe_route``: the router product, softmax,
top-k, the sort of the (token, choice) pairs, the gather of their rows and the
weighted sum back: what routing and moving rows cost beside the products."""

from benchmark.lib import scopes

NAME = "moe_route_ms.train"
UNIT = "ms"
LAYER = "experts layer"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scopes.scope_ms(run, "moe_route")
