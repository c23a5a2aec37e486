"""Device time a step of the backward pass: the ops under the step parts
``model_apply`` and ``criterion`` whose text holds ``transpose(jvp(``, which
JAX writes itself (``lib/step_parts.py``); with ``nn.Remat`` the recomputed
forward is in here, since it runs inside the transposed program."""

from benchmark.lib import step_parts

NAME = "bwd_ms.train"
UNIT = "ms"
LAYER = "jitted train step"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return step_parts.part_ms(run, ("model_apply", "criterion"), backward=True)
