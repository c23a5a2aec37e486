"""How unevenly the router loads the experts held: the step records'
``moe_load_max_over_mean`` (largest group over the mean group, worst layer),
mean over the window. 1 is even; the grouped products' tiles and, in a
deployment, the slowest chip of the exchange follow the largest group."""

from benchmark.lib import scopes

NAME = "moe_load_max_over_mean.train"
UNIT = "ratio"
LAYER = "experts layer"
MOVES = "train_records_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    return scopes.counter_mean(run, "moe_load_max_over_mean")
