"""Device time a step of the forward pass: the ops under the step parts
``model_apply`` and ``criterion`` whose text does not hold ``transpose(jvp(``
(``lib/step_parts.py``). A forward recomputed under ``nn.Remat`` sits inside
the transposed program and counts as backward, not here."""

from benchmark.lib import step_parts

NAME = "fwd_ms.train"
UNIT = "ms"
LAYER = "jitted train step"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return step_parts.part_ms(run, ("model_apply", "criterion"), backward=False)
