"""What the dataset layer delivers: host bytes handed to the placement seam
(the step records' ``h2d_bytes``, a ``program_counter``) over the seconds the
prefetch worker spent in ``next(src)`` (its ``dataset_next`` span), summed
over the window. One worker thread, so this is the input path's ceiling."""

from benchmark.lib import spans

NAME = "input_gbps.train"
UNIT = "GB/s"
LAYER = "dataset + _prefetch"
MOVES = "train_records_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    moved = sum(r.get("h2d_bytes") or 0 for r in run.steps)
    busy = sum(spans.seconds(run.steps, "dataset_next"))
    return moved / busy / 1e9 if moved and busy else None
