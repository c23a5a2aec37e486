"""Device time a step in the scope ``mla_proj``: latent attention's two
low-rank paths (2048 -> 1536 -> 32 x 192 and 2048 -> 576 -> 32 x 256), their
norms, RoPE on the 64 rotary dims, the concatenations and the output
projection 4096 -> 2048 of every block (the MTP module's ``eh_proj`` too),
forward and backward, recomputation included."""

from benchmark.lib import scope_names, scope_times

NAME = "mla_proj_ms.train"
UNIT = "ms"
LAYER = "latent attention"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scope_times.scope_ms(run, "mla_proj", scope_names.LATENT_SCOPES)
