"""Device time of one step: the union of the device-op intervals over the
traced steady steps, per step."""

NAME = "device_step_ms.train"
UNIT = "ms"
LAYER = "jitted train step"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    t = run.trace
    return t.busy_s / t.steps * 1e3 if t is not None and t.steps else None
