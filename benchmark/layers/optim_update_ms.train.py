"""Device time a step of the optimizer's update: the ops under the step part
``optim_update`` (the clip and ``method.update`` / ``update_flat`` of the step
builders; ``lib/step_parts.py``). What XLA fuses into a dW product keeps that
fusion's owner, so an update that rides behind its gradient's product is
counted with the backward, not here."""

from benchmark.lib import step_parts

NAME = "optim_update_ms.train"
UNIT = "ms"
LAYER = "optimizer update"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return step_parts.part_ms(run, ("optim_update",))
