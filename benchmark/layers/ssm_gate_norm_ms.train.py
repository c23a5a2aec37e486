"""Device time a step in the scope ``ssm_gate_norm``: the state-space layers'
gate ``y * silu(z)`` and the gated RMSNorm between scan and output projection
(its statistic over each B/C group's channels), forward and backward,
recomputation included: elementwise passes over (tokens, d_inner) arrays and
no product. The scope lies inside ``ssm_proj``, so ``ssm_proj_ms.train`` holds
this time too; a program without the scope gives nothing."""

from benchmark.lib import scope_times

NAME = "ssm_gate_norm_ms.train"
UNIT = "ms"
LAYER = "state-space layer"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scope_times.scope_ms(run, "ssm_gate_norm", ("ssm_gate_norm",))
