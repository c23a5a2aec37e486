"""Generator ``token_records``: int32 token records for a language model,
held in host memory and served by ``DataSet.array``. A record is
``record_tokens`` ids and its label the same ids one position on, so
``record_tokens + 1`` are drawn a record. Every seed gives the same number of
records of the same shape; only the ids and the shuffle differ.

Mix parameters: ``batches_per_epoch`` (records = that x batch x chips),
``record_tokens``, ``tokens`` (``{"law": "zipf", "exponent": s}`` over the
configuration's ``vocab_size``).
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np


def token_law(params: dict, vocab: int) -> np.ndarray:
    if params["law"] != "zipf":
        raise ValueError(f"token_records: unknown token law {params['law']!r}")
    p = 1.0 / np.arange(1, vocab + 1) ** float(params["exponent"])
    return p / p.sum()


def draw(params: dict, cfg: dict, seed: int, records: int) -> np.ndarray:
    """(records, record_tokens + 1) int32 ids from the mix's law and seed."""
    vocab = int(cfg["vocab_size"])
    rng = np.random.default_rng(seed)
    # inverse-CDF sampling: one pass over uniform draws (rng.choice with p
    # builds the same table but is several times slower at this size)
    cdf = np.cumsum(token_law(params["tokens"], vocab))
    u = rng.random((records, int(params["record_tokens"]) + 1))
    return np.minimum(np.searchsorted(cdf, u), vocab - 1).astype(np.int32)


def make(params: dict, cfg: dict, seed: int, chips: int):
    """-> namespace(dataset, batch, steps_per_epoch, records)."""
    from bigdl_tpu.dataset import DataSet

    batch = int(cfg["deployment"]["batch_per_chip"]) * chips
    steps = int(params["batches_per_epoch"])
    tokens = draw(params, cfg, seed, steps * batch)
    x = np.ascontiguousarray(tokens[:, :-1])
    y = np.ascontiguousarray(tokens[:, 1:])
    return SimpleNamespace(
        dataset=DataSet.array(x, y, batch_size=batch),
        batch=batch, steps_per_epoch=steps, records=steps * batch)
