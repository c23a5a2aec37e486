"""Generator ``record_shards``: packed record files read back through the
input pipeline: the path of every ImageNet job of the reference
(``DataSet.SeqFileFolder``), here ``write_record_shards`` files under
``DataPipeline(ShardedRecordDataSet(...))``. Every seed gives the same number
of records of the same size; only the values, the labels and the shuffles
differ.

The shards are written at set-up (inside ``setup_s``) to a directory of their
own under ``.scratch/benchmark/`` that the process removes when it ends; the
run reads them from the page cache, so the cell measures the reader, the
decode and the batch assembly, not a disk.

Mix parameters: ``batches_per_epoch`` (records = that x batch x chips),
``records_per_shard``, ``num_workers`` (the pipeline's), ``labels`` (as
``array_records``). A record is a ``uint8`` height x width x channels payload
of the configuration's input shape with an int64 label; ``decode`` is
``examples/resnet/train.py: load_imagenet``'s.
"""

from __future__ import annotations

import atexit
import os
import shutil
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import numpy as np

from benchmark.traffic.array_records import _label_law

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def decoder(height: int, width: int, channels: int):
    """``load_imagenet``'s decode: uint8 HWC -> float32, / 255, - 0.449,
    / 0.226, -> CHW."""
    from bigdl_tpu.dataset import Sample

    def decode(payload, label):
        img = np.frombuffer(payload, np.uint8).reshape(height, width, channels)
        x = (img.astype(np.float32) / 255.0 - 0.449) / 0.226
        return Sample(x.transpose(2, 0, 1), np.int64(label))

    return decode


def make(params: dict, cfg: dict, seed: int, chips: int):
    """-> namespace(dataset, batch, steps_per_epoch, records, shards, labels)."""
    from bigdl_tpu.dataset import (DataPipeline, ShardedRecordDataSet,
                                   write_record_shards)

    batch = int(cfg["deployment"]["batch_per_chip"]) * chips
    steps = int(params["batches_per_epoch"])
    channels, height, width = cfg["model"]["input_shape"]
    classes = int(cfg["model"]["class_num"])
    per_shard = int(params["records_per_shard"])
    records = steps * batch
    directory = os.path.join(ROOT, ".scratch", "benchmark",
                             f"record_shards_{seed}_{os.getpid()}")
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    atexit.register(shutil.rmtree, directory, ignore_errors=True)

    rng = np.random.default_rng(seed)
    # one random batch, tiled with an offset per record (as array_records):
    # the values do not matter to speed, and drawing every record would
    # dominate set-up
    base = rng.integers(0, 256, (batch, height, width, channels), np.uint8)
    offsets = rng.integers(0, 256, records, np.uint8)
    labels = rng.choice(classes, size=records,
                        p=_label_law(params["labels"], classes)).astype(np.int64)

    def write(k: int):
        rows = range(k * per_shard, min((k + 1) * per_shard, records))
        return write_record_shards(
            (((base[i % batch] + offsets[i]).tobytes(), labels[i])
             for i in rows),
            directory, records_per_shard=per_shard, prefix=f"part-{k:04d}")

    n_shards = -(-records // per_shard)
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        shards = [p for paths in pool.map(write, range(n_shards))
                  for p in paths]
    source = ShardedRecordDataSet(
        shards, decoder(height, width, channels), batch_size=batch)
    return SimpleNamespace(
        dataset=DataPipeline(source, num_workers=int(params["num_workers"])),
        batch=batch, steps_per_epoch=steps, records=records, shards=shards,
        labels=labels)
