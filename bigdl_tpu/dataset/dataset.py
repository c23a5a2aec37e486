"""Data pipeline core (reference: ``$DL/dataset/DataSet.scala``, ``Sample.scala``,
``MiniBatch.scala``, ``Transformer.scala``).

Reference behavior: ``DataSet`` factories produce Local or Distributed datasets;
``Transformer[A,B]`` chains (composed with ``->``) turn raw records into ``Sample``s
and then ``MiniBatch``es; distributed datasets serve an infinite shuffled iterator
per partition with "partition ↔ device 1:1".

TPU-native design: batches are pytrees of numpy arrays assembled on the HOST (the
analog of executor-side CPU preprocessing), handed to the device (or device mesh)
by the optimizer. A ``DistributedDataSet`` shards each global batch into
per-device sub-batches along the leading axis — the partition↔device 1:1 mapping.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..utils.random import RandomGenerator


class Sample:
    """One record: feature pytree + label pytree (reference: ``Sample``/``ArraySample``)."""

    __slots__ = ("feature", "label")

    def __init__(self, feature, label=None):
        self.feature = feature
        self.label = label

    def __repr__(self):
        f = np.shape(self.feature)
        return f"Sample(feature{f}, label={self.label!r})"


class MiniBatch:
    """Batched features+labels (reference: ``MiniBatch``); ``slice`` mirrors the
    per-thread sub-batching the reference used for thread-level DP — here it shards
    a global batch across mesh devices.

    ``host_lease`` is set by a dataset that assembled ``input`` in a buffer
    it would take back (:class:`HostBufferLease`); it rides on the batch
    object, so a stream that passes batches through passes it along. A stream
    that keeps a batch beyond the next one it yields must not pass it on."""

    host_lease: Optional["HostBufferLease"] = None

    def __init__(self, input, target=None):
        self.input = input
        self.target = target

    def size(self) -> int:
        from ..utils.table import Table

        leaf = self.input
        while isinstance(leaf, (dict, list, tuple, Table)):
            if isinstance(leaf, Table):
                leaf = next(iter(leaf.values()))
            elif isinstance(leaf, dict):
                leaf = next(iter(leaf.values()))
            else:
                leaf = leaf[0]
        return int(leaf.shape[0] if hasattr(leaf, "shape") else np.shape(leaf)[0])

    def get_input(self):
        return self.input

    def get_target(self):
        return self.target

    def slice(self, offset: int, length: int) -> "MiniBatch":
        import jax

        sl = jax.tree_util.tree_map(lambda a: a[offset : offset + length], self.input)
        tg = (
            None
            if self.target is None
            else jax.tree_util.tree_map(lambda a: a[offset : offset + length], self.target)
        )
        return MiniBatch(sl, tg)


def pad_minibatch(batch: "MiniBatch", total: int):
    """Pad a ragged MiniBatch to ``total`` rows by repeating row 0, returning
    ``(padded_batch, n_real)`` — or ``None`` when any leaf is not a dense
    array batched on its leading axis (sparse columns and scalar targets
    cannot be row-padded).

    This is the dataset→prefetch seam half of the ragged-batch story: the
    optimizer pads the final short batch of an epoch to the step's static
    shape and masks the pad rows out of the loss (``criterion.unreduced``),
    so a multi-epoch fit compiles its train step exactly once instead of
    once per distinct tail shape. Host-side numpy only — it runs inside the
    prefetch thread, before the device transfer."""
    import jax  # local: dataset assembly must not force jax at module import

    n = batch.size()
    if n >= total:
        return batch, n

    def pad_tree(tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        out = []
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            if not shape or shape[0] != n:
                return None
            a = np.asarray(leaf)
            pad = np.broadcast_to(a[:1], (total - n,) + a.shape[1:])
            out.append(np.concatenate([a, pad], axis=0))
        return jax.tree_util.tree_unflatten(treedef, out)

    x = pad_tree(batch.get_input())
    if x is None:
        return None
    t = batch.get_target()
    if t is not None:
        t = pad_tree(t)
        if t is None:
            return None
    return MiniBatch(x, t), n


class HostBuffers:
    """A dataset's free list of host batch buffers, all of one shape and dtype.

    A batch-sized ``np.empty`` is a fresh mapping: the kernel faults in and
    zeroes every 4 KB page of it the first time the gather writes there, which
    costs several times the copy itself. A buffer that was written before
    costs the copy alone. So :meth:`lease` takes from the list when it can,
    and a buffer comes back ONLY through :meth:`HostBufferLease.hand_back`,
    called by a consumer that knows nothing reads it any more (the prefetch
    worker, once the host→device copy is done and no device array lives in
    it). A consumer that never hands back gets a fresh array per batch, as
    before. The list therefore never holds more than were in flight at once.

    :meth:`clear` (the end of a run; a batch of another shape) also cuts off
    every lease made before it, so a worker thread that outlives its run
    cannot refill the list."""

    # where a miss gets its memory; a test substitutes an allocator whose
    # arrays the CPU client aliases
    _allocate = staticmethod(np.empty)

    def __init__(self):
        self._lock = threading.Lock()
        self._free: List[np.ndarray] = []
        self._key: Optional[Tuple] = None
        self._round = 0

    def __len__(self) -> int:
        return len(self._free)

    def __reduce__(self):
        return (HostBuffers, ())  # a copy of the dataset starts with none

    def lease(self, shape, dtype) -> "HostBufferLease":
        key = (tuple(shape), np.dtype(dtype))
        with self._lock:
            if key != self._key:
                self._key = key
                self._drop()
            buf = self._free.pop() if self._free else None
            rnd = self._round
        reused = buf is not None
        if buf is None:
            buf = self._allocate(key[0], key[1])
        return HostBufferLease(self, buf, reused, rnd)

    def clear(self) -> None:
        with self._lock:
            self._drop()

    def _drop(self) -> None:  # under the lock
        self._free.clear()
        self._round += 1

    def _give(self, buf: np.ndarray, rnd: int) -> None:
        with self._lock:
            if rnd == self._round:
                self._free.append(buf)


class HostBufferLease:
    """One batch's hold on a buffer of a :class:`HostBuffers` list.
    ``reused`` says whether the buffer came from the list (warm) or from
    ``np.empty`` (a miss)."""

    __slots__ = ("buffer", "reused", "pool", "_round")

    def __init__(self, pool: HostBuffers, buffer: np.ndarray, reused: bool,
                 rnd: int):
        self.pool, self.buffer, self.reused, self._round = (
            pool, buffer, reused, rnd)

    def hand_back(self) -> None:
        """The caller vouches that nothing reads ``buffer`` or lives in its
        memory any more. Only the first call returns it."""
        buf, self.buffer = self.buffer, None
        if buf is not None:
            self.pool._give(buf, self._round)


class Transformer:
    """Iterator→Iterator stage; compose with ``//`` or ``.and_then`` (the reference
    composes with ``->``, which Python cannot overload)."""

    def apply(self, it: Iterator) -> Iterator:
        raise NotImplementedError

    def __call__(self, it):
        return self.apply(iter(it))

    def and_then(self, other: "Transformer") -> "Transformer":
        return _Chained(self, other)

    def __floordiv__(self, other: "Transformer") -> "Transformer":
        return self.and_then(other)


class _Chained(Transformer):
    def __init__(self, first: Transformer, second: Transformer):
        self.first, self.second = first, second

    def apply(self, it):
        return self.second.apply(self.first.apply(it))


class Lambda(Transformer):
    def __init__(self, fn: Callable[[Any], Any]):
        self.fn = fn

    def apply(self, it):
        return (self.fn(x) for x in it)


class SampleToMiniBatch(Transformer):
    """Group Samples into MiniBatches (reference: ``SampleToMiniBatch`` with
    optional ``PaddingParam`` for variable-length features)."""

    def __init__(self, batch_size: int, padding_value: Optional[float] = None,
                 drop_remainder: bool = False):
        self.batch_size = batch_size
        self.padding_value = padding_value
        self.drop_remainder = drop_remainder

    def _stack(self, items: List[np.ndarray]) -> np.ndarray:
        if self.padding_value is not None:
            max_len = max(np.shape(i)[0] for i in items)
            items = [
                np.pad(
                    np.asarray(i),
                    [(0, max_len - np.shape(i)[0])] + [(0, 0)] * (np.ndim(i) - 1),
                    constant_values=self.padding_value,
                )
                for i in items
            ]
        return np.stack([np.asarray(i) for i in items])

    def apply(self, it):
        buf: List[Sample] = []
        for s in it:
            buf.append(s)
            if len(buf) == self.batch_size:
                yield self._to_batch(buf)
                buf = []
        if buf and not self.drop_remainder:
            yield self._to_batch(buf)

    def _to_batch(self, buf: List[Sample]) -> MiniBatch:
        feats = self._stack([s.feature for s in buf])
        labels = None
        if buf[0].label is not None:
            labels = np.stack([np.asarray(s.label) for s in buf])
        return MiniBatch(feats, labels)


def _epoch_order(n: int, epoch: Optional[int]) -> np.ndarray:
    """Deterministic per-epoch permutation: seeded by (global seed, epoch), so a
    resumed run regenerates the identical order and can skip to its saved data
    position (SURVEY.md §5 checkpoint spec: 'params, opt state, RNG key, data
    position'). With epoch=None, draws from the stateful global stream."""
    if epoch is None:
        order = np.arange(n)
        RandomGenerator.numpy_rng().shuffle(order)
        return order
    return np.random.default_rng((RandomGenerator.get_seed(), int(epoch))).permutation(n)


class AbstractDataSet:
    def size(self) -> int:
        raise NotImplementedError

    def shuffle(self, epoch: Optional[int] = None) -> None:
        pass

    def data(self, train: bool) -> Iterator[MiniBatch]:
        """Finite iterator over one epoch of MiniBatches."""
        raise NotImplementedError


class LocalArrayDataSet(AbstractDataSet):
    """In-memory dataset over (features, labels) arrays (reference: DataSet.array).

    ``transform`` chains run per epoch over shuffled Samples.
    """

    def __init__(self, features, labels=None, transformer: Optional[Transformer] = None,
                 batch_size: int = 32):
        self.features = np.asarray(features)
        self.labels = None if labels is None else np.asarray(labels)
        self.transformer = transformer
        self.batch_size = batch_size
        self._order = np.arange(len(self.features))
        # lives with the dataset, not with an epoch's data() call: a list per
        # epoch would start every epoch on cold buffers
        self._host_buffers = HostBuffers()

    def size(self) -> int:
        return len(self.features)

    def shuffle(self, epoch: Optional[int] = None) -> None:
        self._order = _epoch_order(len(self.features), epoch)

    def _samples(self) -> Iterator[Sample]:
        for i in self._order:
            yield Sample(
                self.features[i], None if self.labels is None else self.labels[i]
            )

    def samples(self, train: bool) -> Iterator[Sample]:
        """Record-level sample stream in epoch order — the
        :class:`~bigdl_tpu.dataset.pipeline.DataPipeline` source seam."""
        return self._samples()

    def data(self, train: bool) -> Iterator[MiniBatch]:
        if self.transformer is None and isinstance(self.features, np.ndarray):
            # fast path: assemble whole minibatches with one (native-threaded
            # when built — see bigdl_tpu.native) row gather per batch instead
            # of per-sample stacking, into a buffer a consumer handed back
            # when there is one (HostBuffers)
            from ..native import gather_rows

            bs = self.batch_size
            n = len(self._order)
            for start in range(0, n, bs):
                idx = self._order[start:start + bs]
                if train and len(idx) < bs:
                    break  # reference drops ragged train batches
                lease = self._host_buffers.lease(
                    (len(idx),) + self.features.shape[1:], self.features.dtype)
                x = gather_rows(self.features, idx, out=lease.buffer)
                t = None if self.labels is None else self.labels[idx]
                batch = MiniBatch(x, t)
                batch.host_lease = lease
                yield batch
            return
        it: Iterator = self._samples()
        t = self.transformer
        if t is None:
            t = SampleToMiniBatch(self.batch_size, drop_remainder=train)
        yield from t.apply(it)


class BucketedTextDataSet(AbstractDataSet):
    """Variable-length sequences batched by length bucket.

    The ragged-batch story end to end: sequences are grouped by the
    smallest bucket boundary that fits them, each bucket emits batches
    padded (``pad_id``, TRAILING) to ITS boundary — so downstream the
    structural ``lengths`` masking (flash kernel / ring attention /
    ``Transformer(pad_masking='lengths')``) sees far less padding than
    one global max-length pad, at the cost of one jit compilation per
    distinct bucket shape (keep the boundary list short: 3-5 buckets).

    TPU-native framing of TF's ``bucket_by_sequence_length`` — shapes
    stay STATIC per bucket, only the bucket choice is dynamic (resolved
    on the host, never inside jit). Sequences longer than the last
    boundary are truncated to it (recorded in ``truncated_count``).
    Batch order is shuffled across buckets per epoch so training doesn't
    see all short sequences first.
    """

    def __init__(self, sequences, labels=None, boundaries=(64, 128, 256),
                 batch_size: int = 32, pad_id: int = 0):
        if not boundaries or list(boundaries) != sorted(set(boundaries)):
            raise ValueError(
                f"boundaries must be ascending and unique, got {boundaries}")
        self.boundaries = tuple(int(b) for b in boundaries)
        self.batch_size = batch_size
        self.pad_id = pad_id
        if pad_id != 0:
            import warnings

            # the structural masking helpers this dataset exists to feed
            # (lengths_from_ids, pad_masking='bias') hardcode pad id 0 —
            # a nonzero pad would be silently attended to
            warnings.warn(
                f"pad_id={pad_id}: the framework's lengths/pad masking "
                "assumes pad id 0; nonzero pads are NOT masked by "
                "Transformer(pad_masking=...)", stacklevel=3)
        self.labels = None if labels is None else np.asarray(labels)
        self._buckets = {b: [] for b in self.boundaries}  # boundary -> [idx]
        self.truncated_count = 0
        self._seqs = []
        for i, s in enumerate(sequences):
            s = np.asarray(s)
            if s.ndim != 1:
                raise ValueError(
                    f"sequence {i} has shape {s.shape}; expected 1-D ids")
            if len(s) > self.boundaries[-1]:
                s = s[: self.boundaries[-1]]
                self.truncated_count += 1
            self._seqs.append(s)
            for b in self.boundaries:
                if len(s) <= b:
                    self._buckets[b].append(i)
                    break
        if self.labels is not None and len(self.labels) != len(self._seqs):
            raise ValueError(
                f"{len(self.labels)} labels for {len(self._seqs)} sequences")
        # one dtype for every batch: nondeterministic per-batch dtypes would
        # retrace jit per dtype and silently wrap-cast mixed-width rows
        self._dtype = (np.result_type(*self._seqs) if self._seqs
                       else np.dtype(np.int32))
        self._epoch = 0

    def size(self) -> int:
        return len(self._seqs)

    def shuffle(self, epoch: Optional[int] = None) -> None:
        self._epoch = epoch if epoch is not None else self._epoch + 1

    def _batches_of(self, b: int, rng) -> list:
        idx = np.asarray(self._buckets[b], dtype=np.int64)
        if rng is not None:
            idx = idx[rng.permutation(len(idx))]
        return [(b, idx[s:s + self.batch_size])
                for s in range(0, len(idx), self.batch_size)]

    def data(self, train: bool) -> Iterator[MiniBatch]:
        from ..utils.random import RandomGenerator

        # seeded like _epoch_order: the global seed drives data order so
        # seed sweeps vary it and checkpoint-resume reproduces it
        rng = np.random.default_rng(
            (RandomGenerator.get_seed(), self._epoch))
        batches = []
        for b in self.boundaries:
            batches.extend(self._batches_of(b, rng if train else None))
        if train:
            batches = [batches[i] for i in rng.permutation(len(batches))]
        for b, idx in batches:
            if train and len(idx) < self.batch_size:
                continue  # reference drops ragged train batches
            x = np.full((len(idx), b), self.pad_id, self._dtype)
            for row, i in enumerate(idx):
                s = self._seqs[i]
                x[row, : len(s)] = s
            t = None if self.labels is None else self.labels[idx]
            yield MiniBatch(x, t)


class LocalTableDataSet(AbstractDataSet):
    """Dataset over a ``Table`` of feature columns, any of which may be a
    ``SparseTensor`` — the SparseMiniBatch analog (reference:
    ``$DL/dataset/MiniBatch.scala`` SparseMiniBatch, feeding wide&deep).

    TPU-native design: every batch's sparse column is emitted with a FIXED nnz
    capacity (``batch_size * max_nnz_per_row``, zero-padded with inert
    (row 0, col 0, val 0) entries) so the jitted train step never retraces on
    nnz variation — static shapes are what the compiler needs.
    """

    def __init__(self, features, labels=None, batch_size: int = 32):
        from ..tensor.sparse import SparseTensor
        from ..utils.table import Table

        if not isinstance(features, Table):
            raise TypeError("LocalTableDataSet needs a Table of feature columns")
        self._keys = list(features.keys())
        self._cols = list(features.values())
        self.labels = None if labels is None else np.asarray(labels)
        self.batch_size = batch_size
        ns = {c.shape[0] for c in self._cols}
        if len(ns) != 1:
            raise ValueError(f"feature columns disagree on row count: {ns}")
        self.n = ns.pop()
        self._order = np.arange(self.n)
        # host-side CSR prep per sparse column: rows sorted, slice offsets
        self._sparse = {}
        for j, c in enumerate(self._cols):
            if isinstance(c, SparseTensor):
                rows = np.asarray(c.row_indices)
                cols = np.asarray(c.col_indices)
                vals = np.asarray(c.values)
                order = np.argsort(rows, kind="stable")
                rows, cols, vals = rows[order], cols[order], vals[order]
                counts = np.bincount(rows, minlength=self.n)
                starts = np.concatenate([[0], np.cumsum(counts)])
                self._sparse[j] = (cols, vals, starts, int(counts.max()))
            else:
                self._cols[j] = np.asarray(c)

    def size(self) -> int:
        return self.n

    def shuffle(self, epoch: Optional[int] = None) -> None:
        self._order = _epoch_order(self.n, epoch)

    def _slice_sparse(self, j: int, idx: np.ndarray, n_cols: int):
        from ..tensor.sparse import SparseTensor

        cols, vals, starts, max_per_row = self._sparse[j]
        cap = len(idx) * max_per_row
        out_r = np.zeros(cap, np.int32)
        out_c = np.zeros(cap, np.int32)
        out_v = np.zeros(cap, vals.dtype)
        k = 0
        for p, i in enumerate(idx):
            s, e = starts[i], starts[i + 1]
            m = e - s
            out_r[k:k + m] = p
            out_c[k:k + m] = cols[s:e]
            out_v[k:k + m] = vals[s:e]
            k += m
        return SparseTensor.from_coo(out_r, out_c, out_v, (len(idx), n_cols))

    def data(self, train: bool) -> Iterator[MiniBatch]:
        from ..utils.table import T

        bs = self.batch_size
        for start in range(0, self.n, bs):
            idx = self._order[start:start + bs]
            if train and len(idx) < bs:
                break  # reference drops ragged train batches
            cols_out = []
            for j, c in enumerate(self._cols):
                if j in self._sparse:
                    cols_out.append(self._slice_sparse(j, idx, c.shape[1]))
                else:
                    cols_out.append(c[idx])
            t = None if self.labels is None else self.labels[idx]
            yield MiniBatch(T(*cols_out), t)


class DistributedDataSet(AbstractDataSet):
    """Batch-sharding wrapper: serves global batches whose leading dim is divisible
    by the mesh size, so the optimizer can shard partition↔device 1:1
    (reference: ``DistributedDataSet``/``CachedDistriDataSet`` semantics minus Spark).
    """

    def __init__(self, base: AbstractDataSet, n_devices: int):
        self.base = base
        self.n_devices = n_devices

    def size(self) -> int:
        return self.base.size()

    @property
    def supports_skip_positions(self) -> bool:
        """Forwarded from the base dataset (DataPipeline cooperates with the
        FailurePolicy's poison-batch quarantine at the source seam)."""
        return bool(getattr(self.base, "supports_skip_positions", False))

    def shuffle(self, epoch: Optional[int] = None) -> None:
        self.base.shuffle(epoch)

    def data(self, train: bool, skip_positions=None) -> Iterator[MiniBatch]:
        if skip_positions is not None and self.supports_skip_positions:
            inner = self.base.data(train, skip_positions=skip_positions)
        else:
            inner = self.base.data(train)
        return _DivisibleStream(inner, self.n_devices, train)


class _DivisibleStream:
    """DistributedDataSet's divisibility filter as a stream object, keeping
    the base stream's ``qsize``/``close`` surface (the input-starvation
    gauges and early-abandonment shutdown) visible through the wrapper."""

    def __init__(self, inner, n_devices: int, train: bool):
        self._inner = iter(inner)
        self._raw = inner
        self._n = n_devices
        self._train = train

    def __iter__(self) -> "_DivisibleStream":
        return self

    def __next__(self) -> MiniBatch:
        while True:
            batch = next(self._inner)
            if batch.size() % self._n == 0 or not self._train:
                # eval path pads at the consumer; ragged train batches drop
                # (reference drops incomplete minibatches)
                return batch

    def qsize(self) -> int:
        q = getattr(self._raw, "qsize", None)
        return q() if q is not None else 0

    def close(self) -> None:
        c = getattr(self._raw, "close", None)
        if c is not None:
            c()


class DataSet:
    """Factory facade (reference: object DataSet in $DL/dataset/DataSet.scala)."""

    @staticmethod
    def array(features, labels=None, batch_size: int = 32,
              transformer: Optional[Transformer] = None) -> AbstractDataSet:
        from ..utils.table import Table

        if isinstance(features, Table):  # sparse/multi-column (SparseMiniBatch path)
            if transformer is not None:
                raise ValueError("transformer chains are not supported on Table features")
            return LocalTableDataSet(features, labels, batch_size)
        return LocalArrayDataSet(features, labels, transformer, batch_size)

    @staticmethod
    def distributed(base: AbstractDataSet, n_devices: int) -> DistributedDataSet:
        return DistributedDataSet(base, n_devices)

    @staticmethod
    def bucket_by_length(sequences, labels=None, boundaries=(64, 128, 256),
                         batch_size: int = 32, pad_id: int = 0
                         ) -> "BucketedTextDataSet":
        """Length-bucketed batching for variable-length token sequences —
        pairs with the structural ``lengths`` masking (flash/ring
        attention, ``Transformer(pad_masking='lengths')``). See
        :class:`BucketedTextDataSet`."""
        return BucketedTextDataSet(sequences, labels, boundaries,
                                   batch_size, pad_id)

    @staticmethod
    def pipeline(source: AbstractDataSet, transformer: Optional[Transformer] = None,
                 num_workers: int = 4, **kw):
        """Deterministic multi-worker transform/assembly pipeline over a
        record source — see :class:`~bigdl_tpu.dataset.pipeline.DataPipeline`
        (byte-identical batch stream for any worker count)."""
        from .pipeline import DataPipeline

        return DataPipeline(source, transformer, num_workers=num_workers, **kw)

    @staticmethod
    def image_folder(path: str, batch_size: int = 32, **kw):
        """Class-per-subdirectory image tree (reference: DataSet.ImageFolder)."""
        from .files import ImageFolderDataSet

        return ImageFolderDataSet(path, batch_size=batch_size, **kw)

    @staticmethod
    def record_shards(shard_paths, decode, batch_size: int = 32, **kw):
        """Sharded record files (reference: DataSet.SeqFileFolder)."""
        from .files import ShardedRecordDataSet

        return ShardedRecordDataSet(shard_paths, decode, batch_size=batch_size, **kw)
