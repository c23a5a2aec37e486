"""Generator ``array_records``: records of the configuration's input shape held
in host memory and served by ``DataSet.array`` — the in-memory path a BigDL user
starts with. Every seed gives the same number of records of the same shape; only
the values, the labels and the shuffle differ.

Mix parameters: ``batches_per_epoch`` (records = that x batch x chips),
``record_dtype``, ``labels`` (``{"law": "zipf", "exponent": s}`` over the
configuration's classes, or ``{"law": "uniform"}``).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import numpy as np


def _label_law(params: dict, classes: int) -> np.ndarray:
    law = params.get("law", "uniform")
    if law == "uniform":
        return np.full(classes, 1.0 / classes)
    if law == "zipf":
        p = 1.0 / np.arange(1, classes + 1) ** float(params["exponent"])
        return p / p.sum()
    raise ValueError(f"array_records: unknown label law {law!r}")


def make(params: dict, cfg: dict, seed: int, chips: int):
    """-> namespace(dataset, batch, steps_per_epoch, records)."""
    from bigdl_tpu.dataset import DataSet

    batch = int(cfg["deployment"]["batch_per_chip"]) * chips
    steps = int(params["batches_per_epoch"])
    shape = tuple(cfg["model"]["input_shape"])
    classes = int(cfg["model"]["class_num"])
    dtype = np.dtype(params["record_dtype"])
    rng = np.random.default_rng(seed)
    # one random batch, tiled with an offset per record: the values do not
    # matter to speed, and drawing every record would dominate set-up
    base = rng.standard_normal((batch,) + shape, dtype=np.float32).astype(dtype)
    offsets = rng.standard_normal(steps * batch).astype(dtype) * dtype.type(0.1)
    x = np.empty((steps * batch,) + shape, dtype)
    lead = (slice(None),) + (None,) * len(shape)

    def fill(k: int) -> None:
        rows = slice(k * batch, (k + 1) * batch)
        np.add(base, offsets[rows][lead], out=x[rows])

    # first touch of fresh memory is most of the cost, and it scales with
    # threads (numpy releases the interpreter lock); the pool ends here
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, range(steps)))
    y = rng.choice(classes, size=steps * batch,
                   p=_label_law(params["labels"], classes)).astype(np.int32)
    return SimpleNamespace(
        dataset=DataSet.array(x, y, batch_size=batch),
        batch=batch, steps_per_epoch=steps, records=steps * batch)
