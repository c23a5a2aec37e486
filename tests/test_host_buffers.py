"""Recycled host batch buffers (``dataset.HostBuffers``, ``native.gather_rows``'s
``out``, the hand-back in ``_prefetch_batches``): a batch is gathered into a
buffer a consumer handed back, never into one anything still reads or lives
in, and what reaches the step is bit for bit what fresh memory would give."""

import threading
import time

import jax
import numpy as np
import pytest

import bigdl_tpu.native as native
from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.dataset.dataset import (HostBufferLease, HostBuffers,
                                       LocalArrayDataSet)
from bigdl_tpu.obs import Telemetry
from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger
from bigdl_tpu.optim.local_optimizer import _host_buffer_free
from bigdl_tpu.utils.random import RandomGenerator

BATCH, BATCHES, EPOCHS, DIM = 16, 6, 3, 64  # a shard of 2 rows is 512 bytes


@pytest.fixture(scope="module", autouse=True)
def _engine_isolation():
    from bigdl_tpu.utils.engine import Engine

    Engine.reset()
    yield
    Engine.reset()


def _at_offset(offset):
    """An allocator whose arrays start ``offset`` bytes past a 64-byte
    boundary: at 0 the CPU client adopts the memory (``device_put`` and
    ``jnp.asarray`` alias it), at 16 it copies."""

    def allocate(shape, dtype):
        n = int(np.prod(shape)) * np.dtype(dtype).itemsize
        raw = np.empty(n + 128, np.uint8)
        start = (-raw.ctypes.data) % 64 + offset
        return raw[start:start + n].view(dtype).reshape(shape)

    return staticmethod(allocate)


# ------------------------------------------------------- gather_rows(out=)
@pytest.fixture(params=["native", "fallback"])
def gather_path(request, monkeypatch):
    """(src, idx) that take the named path of ``gather_rows``."""
    rng = np.random.default_rng(3)
    if request.param == "native":
        if not native.available() and not (native.build()
                                           and native.available()):
            pytest.skip("native toolchain unavailable")
        src = rng.standard_normal((96, 64, 64)).astype(np.float32)  # 1.5 MB
    else:
        monkeypatch.setattr(native, "_load", lambda: None)
        src = rng.standard_normal((96, 5)).astype(np.float32)
    return src, rng.permutation(96)[:64]


def test_gather_rows_out_is_the_fancy_index(gather_path):
    src, idx = gather_path
    out = np.full((len(idx),) + src.shape[1:], np.nan, np.float32)
    got = native.gather_rows(src, idx, out=out)
    assert got is out
    np.testing.assert_array_equal(out, src[idx])
    # and again into the same memory, as a recycled buffer is
    native.gather_rows(src, idx[::-1], out=out)
    np.testing.assert_array_equal(out, src[idx[::-1]])


def test_gather_rows_out_still_checks_the_indices(gather_path):
    src, idx = gather_path
    out = np.zeros((len(idx),) + src.shape[1:], np.float32)
    for bad in (-1, len(src)):
        with pytest.raises(IndexError):
            native.gather_rows(src, np.r_[idx[:-1], bad], out=out)
    assert not out.any()  # refused before a byte was written


def test_gather_rows_without_out_allocates(gather_path):
    src, idx = gather_path
    a, b = native.gather_rows(src, idx), native.gather_rows(src, idx)
    assert a.ctypes.data != b.ctypes.data and a.flags["C_CONTIGUOUS"]
    np.testing.assert_array_equal(a, src[idx])


@pytest.mark.parametrize("make", [
    lambda: np.zeros((4, 5), np.float32),              # rows
    lambda: np.zeros((8, 6), np.float32),              # row shape
    lambda: np.zeros((8, 5), np.float64),              # dtype
    lambda: np.zeros((5, 8), np.float32).T,            # not C-contiguous
    lambda: np.zeros((8, 10), np.float32)[:, ::2],     # strided
    lambda: np.zeros(40, np.float32).tolist(),         # no array at all
    lambda: np.broadcast_to(np.float32(0), (8, 5)),    # read-only
], ids=["rows", "row_shape", "dtype", "transposed", "strided", "list",
        "readonly"])
def test_gather_rows_refuses_an_out_it_cannot_fill(make):
    src = np.arange(100, dtype=np.float32).reshape(20, 5)
    with pytest.raises(ValueError, match="out must be"):
        native.gather_rows(src, np.arange(8), out=make())


# ------------------------------------------- the free list and its dataset
def _distinct(n=BATCH * BATCHES, dim=DIM, dtype=np.float32):
    x = (np.arange(n * dim).reshape(n, dim) % 997 + np.arange(n)[:, None])
    return x.astype(dtype), (np.arange(n) % 3).astype(np.int32)


@pytest.mark.parametrize("outstanding", [1, 3])
def test_handed_back_buffers_are_gathered_into_again(outstanding):
    x, y = _distinct()
    ds = LocalArrayDataSet(x, y, batch_size=BATCH)
    held, seen, reused = [], set(), []
    for epoch in range(1, EPOCHS + 1):
        ds.shuffle(epoch)
        order = ds._order.copy()
        for i, b in enumerate(ds.data(train=True)):
            rows = order[i * BATCH:(i + 1) * BATCH]
            np.testing.assert_array_equal(b.get_input(), x[rows])
            np.testing.assert_array_equal(b.get_target(), y[rows])
            assert b.get_input() is b.host_lease.buffer
            seen.add(b.get_input().ctypes.data)
            reused.append(b.host_lease.reused)
            held.append(b)
            if len(held) == outstanding:
                held.pop(0).host_lease.hand_back()
    # as many buffers as were out at once, over three epochs of six batches
    assert len(seen) == outstanding
    assert reused == [False] * outstanding + [True] * (
        EPOCHS * BATCHES - outstanding)
    assert len(ds._host_buffers) <= outstanding


def test_no_reuse_without_a_hand_back():
    x, y = _distinct()
    ds = LocalArrayDataSet(x, y, batch_size=BATCH)
    kept = list(ds.data(train=True)) + list(ds.data(train=True))
    assert len({b.get_input().ctypes.data for b in kept}) == 2 * BATCHES
    assert not any(b.host_lease.reused for b in kept)
    for i, b in enumerate(kept):
        i %= BATCHES
        np.testing.assert_array_equal(b.get_input(),
                                      x[i * BATCH:(i + 1) * BATCH])
    assert len(ds._host_buffers) == 0


def test_the_hand_back_reaches_the_base_through_a_wrapper():
    x, y = _distinct()
    base = LocalArrayDataSet(x, y, batch_size=BATCH)
    ds = DataSet.distributed(base, 8)
    first = None
    for b in ds.data(train=True):
        first = first or b.get_input().ctypes.data
        assert b.get_input().ctypes.data == first
        b.host_lease.hand_back()
    assert len(base._host_buffers) == 1


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int64, np.uint8])
def test_every_dtype_of_the_fast_path_recycles(dtype):
    x, _ = _distinct(dtype=dtype)
    ds = LocalArrayDataSet(x, batch_size=BATCH)  # no labels
    for i, b in enumerate(ds.data(train=True)):
        assert b.get_input().dtype == dtype and b.get_target() is None
        np.testing.assert_array_equal(b.get_input(),
                                      x[i * BATCH:(i + 1) * BATCH])
        b.host_lease.hand_back()
        assert b.host_lease.reused == (i > 0)


def test_a_batch_of_another_shape_empties_the_list():
    x, y = _distinct(n=BATCH * 2 + 5)
    ds = LocalArrayDataSet(x, y, batch_size=BATCH)
    sizes = []
    for b in ds.data(train=False):  # evaluation keeps the ragged tail
        sizes.append((b.size(), b.host_lease.reused))
        b.host_lease.hand_back()
    assert sizes == [(BATCH, False), (BATCH, True), (5, False)]
    assert [a.shape for a in ds._host_buffers._free] == [(5, DIM)]
    full = next(iter(ds.data(train=True)))
    assert not full.host_lease.reused and len(ds._host_buffers) == 0


def test_a_transformer_chain_carries_no_lease():
    from bigdl_tpu.dataset.dataset import SampleToMiniBatch

    x, y = _distinct()
    ds = LocalArrayDataSet(x, y, SampleToMiniBatch(BATCH), batch_size=BATCH)
    assert all(b.host_lease is None for b in ds.data(train=True))


def test_clear_cuts_off_the_leases_that_are_out():
    pool = HostBuffers()
    before = pool.lease((4, 4), np.float32)
    pool.clear()  # the run ended while a worker still held `before`
    after = pool.lease((4, 4), np.float32)
    before.hand_back()
    assert len(pool) == 0
    after.hand_back()
    after.hand_back()  # only the first call returns it
    assert len(pool) == 1 and after.buffer is None
    assert isinstance(after, HostBufferLease) and after.pool is pool


def test_a_copied_dataset_starts_with_no_buffers():
    import copy
    import pickle

    x, y = _distinct()
    ds = LocalArrayDataSet(x, y, batch_size=BATCH)
    next(iter(ds.data(train=True))).host_lease.hand_back()
    assert len(ds._host_buffers) == 1
    for other in (copy.deepcopy(ds), pickle.loads(pickle.dumps(ds))):
        assert len(other._host_buffers) == 0
        assert other._host_buffers is not ds._host_buffers


def test_no_buffer_is_out_twice_under_contending_threads():
    """More workers than cores lease, write, check and hand back while one
    keeps clearing: a buffer that two holders got at once, or one that came
    back across a clear(), shows as a foreign stamp."""
    import sys

    pool, stop, errors = HostBuffers(), threading.Event(), []

    def holder(stamp):
        try:
            while not stop.is_set():
                lease = pool.lease((64,), np.int64)
                lease.buffer[:] = stamp
                time.sleep(0)
                if not (lease.buffer == stamp).all():
                    errors.append(stamp)
                lease.hand_back()
        except Exception as e:  # surfaced by the assert below
            errors.append(e)

    def clearer():
        while not stop.is_set():
            pool.clear()
            time.sleep(0.001)

    threads = [threading.Thread(target=holder, args=(i,)) for i in range(16)]
    threads.append(threading.Thread(target=clearer))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        time.sleep(0.5)
    finally:
        stop.set()
        for t in threads:
            t.join(5.0)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert len(pool) <= 16


# ------------------------------------------------ when host memory is free
class _Leaf:
    """Stand-in for a placed device array: readiness is the test's to set,
    its memory is wherever the test says."""

    ready = False

    def __init__(self, pointer=1):
        self.pointer = pointer

    def is_ready(self):
        return type(self).ready

    def unsafe_buffer_pointer(self):
        return self.pointer

    @property
    def addressable_shards(self):
        return [type("Shard", (), {"data": self})()]


@pytest.fixture
def leaf_state():
    _Leaf.ready = False
    yield _Leaf
    _Leaf.ready = False


def test_free_means_ready_and_living_elsewhere(leaf_state):
    buf = np.zeros((4, 16), np.float32)
    inside = buf.ctypes.data + buf.nbytes - 4
    assert _host_buffer_free([_Leaf()], buf) is None  # copy still running
    assert _host_buffer_free([_Leaf(inside)], buf) is None
    leaf_state.ready = True
    assert _host_buffer_free([_Leaf(), _Leaf()], buf) is True
    assert _host_buffer_free([_Leaf(), _Leaf(inside)], buf) is False  # alias
    assert _host_buffer_free([_Leaf(buf.ctypes.data + buf.nbytes)], buf)
    assert _host_buffer_free([buf], buf) is False  # the seam passed it on
    assert _host_buffer_free([], buf) is True


def test_the_cpu_client_adopts_aligned_memory_and_is_found_out():
    adopted = _at_offset(0).__func__((BATCH, DIM), np.float32)
    copied = _at_offset(16).__func__((BATCH, DIM), np.float32)
    adopted[:], copied[:] = 1.0, 1.0
    for put in (jax.device_put, jax.numpy.asarray):
        a, c = put(adopted), put(copied)
        jax.block_until_ready((a, c))
        if a.unsafe_buffer_pointer() != adopted.ctypes.data:
            pytest.skip("this CPU client copies aligned host memory too")
        assert _host_buffer_free([a], adopted) is False
        assert _host_buffer_free([c], copied) is True
        assert _host_buffer_free([a, c], copied) is True


def _model():
    return nn.Sequential(nn.Linear(DIM, 16), nn.Tanh(), nn.Linear(16, 3),
                         nn.LogSoftMax())


def _noting(leases, stream):
    """Pass ``stream`` through, as a wrapper dataset does, noting each lease."""
    for batch in stream:
        leases.append(batch.host_lease)
        yield batch


def test_a_buffer_waits_for_its_copy_and_no_longer(leaf_state):
    """The worker hands nothing back while the placed leaf reports a running
    copy (every batch is a miss, in its own memory), and hands back at its
    next turn once the leaf reports ready."""
    x, y = _distinct(n=BATCH * 12)
    ds = LocalArrayDataSet(x, y, batch_size=BATCH)
    opt = LocalOptimizer(_model(), ds, nn.ClassNLLCriterion())
    opt._place_batch = lambda a, t: (_Leaf(), _Leaf())
    leases = []
    batches = opt._prefetch_batches(_noting(leases, ds.data(train=True)))
    for _ in range(4):
        next(batches)
    assert len(ds._host_buffers) == 0
    leaf_state.ready = True
    made_before = len(leases)  # at most the ring and the one being put more
    rest = list(batches)
    assert len(rest) == 8 and len(leases) == 12
    assert not any(l.reused for l in leases[:made_before])
    assert all(l.reused for l in leases[made_before + 1:])
    assert made_before <= 8
    opt._prefetch_thread.join(5.0)
    assert not opt._prefetch_thread.is_alive()


@pytest.mark.parametrize("policy", ["pad", "drop"])
def test_a_padded_or_dropped_tail_is_free_at_once(policy):
    x, y = _distinct(n=BATCH * 2 + 4)
    ds = LocalArrayDataSet(x, y, batch_size=BATCH)
    opt = LocalOptimizer(_model(), ds, nn.ClassNLLCriterion())
    opt._mask_ragged = policy == "pad"
    leases = []
    placed = list(opt._prefetch_batches(
        _noting(leases, ds.data(train=False))))
    assert len(leases) == 3 and leases[-1].buffer is None  # handed back
    if policy == "pad":  # the pad was a copy
        assert [p.size() for p in placed] == [BATCH, BATCH, 4]  # real rows
        assert placed[-1].get_input().shape[0] == BATCH
        np.testing.assert_array_equal(
            np.asarray(placed[-1].get_input())[:4], x[2 * BATCH:])
    else:
        assert [p.size() for p in placed] == [BATCH, BATCH]
    # detached: the prefetcher counts nothing
    assert {p.host_buf_reused for p in placed} == {None}
    assert {p.h2d_bytes for p in placed} == {None}


# ------------------------------------------------------- through optimize()
class _NoLease(LocalArrayDataSet):
    """The parent's path: every batch in fresh memory, nothing to hand back."""

    def data(self, train):
        for batch in super().data(train):
            batch.host_lease = None
            yield batch


def _fit(kind, dataset_cls=LocalArrayDataSet, end=None):
    RandomGenerator.set_seed(11)
    x, y = _distinct()
    x = (x / 997.0).astype(np.float32)
    ds = dataset_cls(x, y, batch_size=BATCH)
    if kind == "local":
        opt = LocalOptimizer(_model(), ds, nn.ClassNLLCriterion())
    else:
        from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer

        opt = DistriOptimizer(_model(), DataSet.distributed(ds, 8),
                              nn.ClassNLLCriterion(), parameter_sync="sharded")
    opt.set_optim_method(SGD(learningrate=0.1, momentum=0.9))
    opt.set_end_when(end or Trigger.max_epoch(EPOCHS))
    tel = Telemetry()
    opt.set_telemetry(tel)
    opt.optimize()
    jax.block_until_ready(opt.model.get_parameters())
    return opt, ds, tel.ring.steps()


@pytest.fixture(scope="module", params=["local", "zero1"])
def kind(request):
    return request.param


@pytest.fixture(scope="module")
def parents_losses(kind):
    _, _, steps = _fit(kind, _NoLease)
    assert {s["host_buf_reused"] for s in steps} == {None}
    return [s["loss"] for s in steps]


@pytest.mark.parametrize("offset", [0, 16], ids=["aliased", "copied"])
def test_loss_stream_is_the_parents_bit_for_bit(kind, parents_losses, offset,
                                                monkeypatch):
    """Three epochs. At offset 0 ``device_put`` adopts every batch buffer, so
    one handed back would be gathered into under a queued device batch; at 16
    it copies, and recycling engages."""
    monkeypatch.setattr(HostBuffers, "_allocate", _at_offset(offset))
    _, ds, steps = _fit(kind)
    assert len(steps) == BATCHES * EPOCHS
    assert [s["loss"] for s in steps] == parents_losses
    reused = [s["host_buf_reused"] for s in steps]
    assert set(reused) <= {0, 1}  # in every step record of an attached run
    if offset == 0:
        assert sum(reused) == 0  # never handed back: as before
    else:
        assert reused[0] == 0 and sum(reused) >= len(reused) // 2
    assert len(ds._host_buffers) == 0


@pytest.mark.parametrize("end", ["max_epoch", "mid_epoch"])
def test_nothing_is_left_when_the_run_ends(kind, end, monkeypatch):
    monkeypatch.setattr(HostBuffers, "_allocate", _at_offset(16))
    trigger = (Trigger.max_epoch(2) if end == "max_epoch"
               else Trigger.max_iteration(BATCHES + 2))
    opt, ds, steps = _fit(kind, end=trigger)
    assert len(steps) == (2 * BATCHES if end == "max_epoch" else BATCHES + 2)
    assert sum(s["host_buf_reused"] for s in steps) > 0  # it did recycle
    opt._prefetch_thread.join(5.0)
    assert not opt._prefetch_thread.is_alive()
    assert len(ds._host_buffers) == 0 and opt._host_buffers is None
