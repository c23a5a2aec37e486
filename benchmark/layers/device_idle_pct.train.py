"""Share of the traced steady window in which no op ran on the device."""

NAME = "device_idle_pct.train"
UNIT = "%"
LAYER = "device"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    t = run.trace
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
