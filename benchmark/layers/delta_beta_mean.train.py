"""How hard the Gated DeltaNet layers write: the step records'
``delta_beta_mean`` (beta over tokens and heads, mean over the layers), mean
over the window. Beta lies in (0, 2) where negative eigenvalues are allowed;
above 1 the factor I - beta k k^T turns the state's component along k. A
program without the counter gives nothing."""

from benchmark.lib import scopes

NAME = "delta_beta_mean.train"
UNIT = "ratio"
LAYER = "linear-attention layer"
MOVES = "train_records_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    return scopes.counter_mean(run, "delta_beta_mean")
