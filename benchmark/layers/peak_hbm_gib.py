"""Peak device memory on the fullest chip: the allocator's peak of live
buffers plus the compiled train step's temporaries (``drivers/train.py``)."""

NAME = "peak_hbm_gib"
UNIT = "GiB"
LAYER = "device"
MOVES = "train_records_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    return run.memory_peak_bytes / 2**30 if run.memory_peak_bytes else None
