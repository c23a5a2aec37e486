"""How far the routers' selection biases have moved: the step records'
``moe_bias_abs_max`` (largest |b| over the routed layers after the step's
update), mean over the traced steps' records. It grows by the update rate a
step for as long as an expert stays over or under the mean load."""

from benchmark.lib import scopes

NAME = "moe_bias_abs_max.train"
UNIT = "ratio"
LAYER = "experts layer"
MOVES = "train_records_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    return scopes.counter_mean(run, "moe_bias_abs_max", traced=True)
