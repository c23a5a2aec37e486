"""What survives an ``nn.Remat`` boundary.

``nn.Remat`` rematerialises a block: its backward runs the block's forward
again instead of storing its activations. Some values are dear to recompute
and cheap to keep: a kernel's output that the kernel's own backward needs
(the flash kernel's ``out`` and logsumexp: one activation and one row
statistic, against a second run of the whole forward kernel). The code that
produces such a value marks it with :func:`keep`; ``nn.Remat``'s default
policy saves exactly the marked values (:data:`KEPT_NAMES`) and nothing else.
Outside a ``jax.checkpoint`` a mark is an identity.

``take_kept_records`` is the counter that says it engaged: ``Telemetry``
writes what the compiling call's own trace kept into its ``compile`` record
(``remat_kept``), beside ``flash_tiles``.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import math
import threading
import time

import numpy as np
from jax.ad_checkpoint import checkpoint_name

# the one list: every name a kernel may mark, and what Remat's default saves
KEPT_NAMES = ("flash_out", "flash_lse")


class _Blocks(threading.local):
    def __init__(self):
        self.stack = []         # ids of the Remat blocks being traced


_blocks = _Blocks()
_block_ids = itertools.count()
# (when traced, block id, name, shape, dtype) of every value marked inside a
# Remat block that keeps marked values; bounded, for a program nobody observes
_kept: collections.deque = collections.deque(maxlen=4096)
_kept_lock = threading.Lock()


@contextlib.contextmanager
def keeping_block():
    """Around the trace of one Remat block whose policy saves the marked
    values: what :func:`keep` marks inside is recorded against this block."""
    _blocks.stack.append(next(_block_ids))
    try:
        yield
    finally:
        _blocks.stack.pop()


def keep(x, name: str):
    """Mark ``x`` as worth keeping across a Remat boundary under ``name``
    (one of :data:`KEPT_NAMES`)."""
    if name not in KEPT_NAMES:
        raise ValueError(f"keep: {name!r} is not one of {KEPT_NAMES}")
    if _blocks.stack:
        with _kept_lock:
            _kept.append((time.perf_counter(), _blocks.stack[-1], name,
                          tuple(x.shape), np.dtype(x.dtype).name))
    return checkpoint_name(x, name)


def take_kept_records(since: float = 0.0) -> list:
    """What was kept by traces at or after ``since`` (a ``time.perf_counter``
    reading), one entry per distinct (name, shape, dtype): in how many Remat
    ``blocks``, how many ``values`` in all, and the ``bytes`` of one. Forgets
    everything, as ``ops/flash_attention.take_tile_records`` does: what an
    earlier, unobserved trace kept belongs to no record."""
    with _kept_lock:
        events = [e[1:] for e in _kept if e[0] >= since]
        _kept.clear()
    by_value: dict = {}
    for block, *value in events:
        by_value.setdefault(tuple(value), []).append(block)
    return [dict(name=name, shape=list(shape), dtype=dtype,
                 blocks=len(set(blocks)), values=len(blocks),
                 bytes=math.prod(shape) * np.dtype(dtype).itemsize)
            for (name, shape, dtype), blocks in by_value.items()]
