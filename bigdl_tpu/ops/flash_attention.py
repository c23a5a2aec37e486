"""Flash attention as a Pallas TPU kernel.

Exact attention with O(T) memory: the (T, T) logits matrix is never
materialized — the grid's innermost dimension streams k/v blocks through VMEM
one (block_k, d) tile at a time while per-q-block online-softmax state
(running max, denominator, weighted accumulator) persists in VMEM scratch
across grid steps. The two matmuls per tile land on the MXU; masking and the
softmax bookkeeping stay on the VPU.

The reference has no analog (its attention materializes full logits through
gemm — ``$DL/nn/Attention.scala``); this is the "C++-where-native" requirement
honored the TPU way (SURVEY.md §2.6): Pallas compiles through Mosaic to native
TPU code, the same role bigdl-core's JNI kernels play for MKL.

Causal masking uses the aligned-at-end convention for rectangular shapes:
query row i corresponds to global position ``i + Tk - Tq`` (so a single-query
decode step attends to every cached key).

Backward: a Pallas kernel as well — the forward additionally emits the
per-row logsumexp, and ONE backward kernel (``flash_bwd``) streams k/v tiles
past each q tile as the forward does, so the (T, T) probability matrix is
never materialized in either direction. The classic recomputation trick:
``p = exp(s - lse)`` is rebuilt per tile from the saved statistics, ``ds = p *
(dp - delta)`` with ``dp = dO V^T`` and ``delta = rowsum(dO * O)`` precomputed
outside the grid. Every visited tile builds ``p`` and ``dp`` once and feeds
all three gradients from them: ``dV += p^T dO``, ``dK += ds^T Q``, ``dQ += ds
K``. dQ accumulates per q tile; dK and dV accumulate in float32 over the
WHOLE key axis of one K/V head in VMEM (``Tk x (d + d_v) x 4`` bytes, the k
tile's rows addressed by a tile-aligned dynamic slice) and are written out
once when the head — with grouped heads the last query head of its group — is
done. That accumulator is why the kernel asks for ``_VMEM_LIMIT`` instead of
Mosaic's default, and the one thing that can keep a shape off it: where the
key axis is too long for it (``backward_form``: from Tk, the tiles, the head
sizes and the dtype, at trace time) the backward is the older PAIR of kernels
with tile-sized accumulators, ``flash_bwd_dq`` (k/v tiles past a q tile) and
``flash_bwd_dkv`` (q tiles past a k tile), each of which rebuilds ``p`` and
``dp``: 11 passes of the 128-wide matrix unit a tile where the one kernel
makes 8 at q/k heads of 192, 7 against 5 at 128 or 64. Both forms round ``p``
and ``ds`` to the operand dtype at the same places and meet a k tile's q
tiles in the same order, so their gradients agree bit for bit.

Masked tiles (the causal diagonal's, a window's edge, a sequence's end)
are where the work exceeds what the mask keeps: under a window of 512 in
tiles of 1024 every visited tile is masked and a head computes 3.87 times
the pairs it keeps. Every kernel computes such a tile as (sub x sub)
sub-blocks (``sub_block``: from the tiles and the mask, at trace time),
skips those the mask wholly hides and masks only those it cuts; a full tile
stays one product, and a tile no larger than a sub-block runs as one tile.

Used via ``scaled_dot_product_attention(..., impl='flash')`` in
``bigdl_tpu.nn.attention`` (TPU backend only; dense fallback elsewhere) or
directly. ``interpret=True`` runs the kernel in the Pallas interpreter (CPU)
— how the unit tests exercise it off-TPU.
"""

from __future__ import annotations

import math
import threading
import time
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.compat import pallas_call
from ..utils.remat_keep import keep

NEG_BIG = -1e30


class _Part(NamedTuple):
    """What one product of a kernel covers: a whole tile, or one sub-block
    of a masked tile. ``qi``, ``kj``: its q and k block indices at its own
    sizes ``bq``, ``bk``; ``rows``, ``cols``: where it lies in the tile's
    q rows and k rows."""
    qi: object
    kj: object
    bq: int
    bk: int
    rows: object = slice(None)
    cols: object = slice(None)


def _each_sub_block(accumulate, kl, qi, j, *, block_q: int, block_k: int,
                    sub: int, causal: bool, causal_offset: int, mask_q: bool,
                    window: Optional[int]):
    """Runs ``accumulate(masked, part)`` on the (sub x sub) sub-blocks of the
    masked tile (q tile ``qi``, k tile ``j``) that the mask does not wholly
    hide, each classified by ``_classify`` at its own size: a wholly visible
    one skips the mask, a wholly hidden one is skipped. Row strip by row
    strip (unrolled: each keeps static slices of the row state), and in a
    strip its sub-blocks in order; every kernel visits them in this order,
    so both backward forms sum alike."""
    nr, nc = block_q // sub, block_k // sub
    for r in range(nr):
        def step(c, carry, r=r):
            part = _Part(qi * nr + r, j * nc + c, sub, sub,
                         slice(r * sub, (r + 1) * sub),
                         pl.ds(pl.multiple_of(c * sub, sub), sub))
            visible, full = _classify(
                kl, part.qi, part.kj, block_q=sub, block_k=sub,
                causal=causal, causal_offset=causal_offset, mask_q=mask_q,
                window=window, nk_real=None)
            pl.when(full)(lambda: accumulate(False, part))
            pl.when(visible & jnp.logical_not(full))(
                lambda: accumulate(True, part))
            return carry

        lax.fori_loop(0, nc, step, 0)


def _tile_or_sub_blocks(accumulate, visible, full, kl, qi, j, *,
                        block_q: int, block_k: int, sub: Optional[int],
                        **classify):
    """A kernel's step on the tile pair (q tile ``qi``, k tile ``j``): a full
    tile is one ``accumulate(False, tile)``; a masked one is one
    ``accumulate(True, tile)`` or, with ``sub``, its sub-blocks that the
    mask does not wholly hide (``_each_sub_block``)."""
    tile = _Part(qi, j, block_q, block_k)

    @pl.when(full)
    def _tile_full():
        accumulate(False, tile)

    @pl.when(visible & jnp.logical_not(full))
    def _tile_masked():
        if sub is None:
            accumulate(True, tile)
        else:
            _each_sub_block(accumulate, kl, qi, j, block_q=block_q,
                            block_k=block_k, sub=sub, **classify)


def _fwd_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                acc_ref, *, block_q: int, block_k: int, causal: bool,
                scale: float, causal_offset: int, t_real_k: int, nk: int,
                has_lengths: bool, mask_q: bool, window: Optional[int] = None,
                nk_real: Optional[int] = None, sub: Optional[int] = None):
    """Grid (BH, num_q_blocks, num_k_blocks); innermost dim streams k/v tiles.

    With a ``window`` the innermost dim has only the ``nk`` k/v tiles a q tile
    can see (``_window_start`` names the first of them; ``nk_real`` is how
    many the keys have), so tiles outside the window are never visited.
    With ``sub`` a masked tile is computed sub-block by sub-block
    (``_each_sub_block``), each row strip with its own slice of the
    online-softmax state.

    q_ref (1, block_q, D) and o_ref depend on (b, i); k_ref/v_ref
    (1, block_k, D) on (b, j). Online-softmax state persists in VMEM scratch
    across the j steps: initialized at j == 0, output written at j == nk-1.

    ``lens_ref`` is a scalar-prefetch (SMEM) array of per-(batch*head) valid
    lengths; with ``has_lengths`` the effective key/query horizon becomes
    ``min(t_real_k, lens_ref[b])`` — tile classification turns into runtime
    predicates, so whole key tiles past a sequence's real length are still
    skipped per batch element, and padded QUERY rows are masked out too (no
    gradient leaks in from dO at padded positions).
    """
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    last = j == nk - 1
    if window is not None:  # j counts from the first tile inside the window
        j = j + _window_start(qi, block_q, block_k, causal_offset, window)

    # Tile classification (scalar arithmetic on program ids):
    #   - invisible tiles (past the real key length / fully beyond the causal
    #     horizon) are skipped entirely — halves causal square work;
    #   - FULL tiles (every entry visible) skip the iota/where mask math —
    #     the VPU bookkeeping, not the MXU dots, is the kernel's bottleneck,
    #     and interior tiles are the vast majority at long T.
    kl = jnp.minimum(lens_ref[pl.program_id(0)], t_real_k) if has_lengths \
        else t_real_k
    visible, full = _classify(
        kl, qi, j, block_q=block_q, block_k=block_k, causal=causal,
        causal_offset=causal_offset, mask_q=has_lengths and mask_q,
        window=window, nk_real=nk_real)

    # with sub-blocks the row statistics m and l are kept as columns
    # repeated over 128 lanes ((block_q, 128) scratch): a sub-block then
    # reads and writes its rows of them without turning a row vector
    # between the lane and the sublane axis, which costs as much for a
    # sub-block's few rows as for a whole tile's
    columns = sub is not None

    def column(x):  # a row statistic as a (rows, 1) column
        return x[:, :1] if columns else x[:, None]

    def _accumulate(masked: bool, part: _Part):
        # MXU dots run in the INPUT dtype (callers pass bf16 under the mixed-
        # precision policy, f32 for exact paths) with f32 accumulation; softmax
        # bookkeeping is always f32, and the scale applies to the f32 product.
        q = q_ref[0, part.rows]
        k = k_ref[0, part.cols]
        v = v_ref[0, part.cols]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # MXU

        if masked:
            cols = part.kj * part.bk + lax.broadcasted_iota(
                jnp.int32, (part.bq, part.bk), 1
            )
            allowed = cols < kl
            if causal or (has_lengths and mask_q):
                rows = part.qi * part.bq + lax.broadcasted_iota(
                    jnp.int32, (part.bq, part.bk), 0
                )
                if has_lengths and mask_q:
                    allowed = allowed & (rows + causal_offset < kl)
                if causal:
                    allowed = allowed & (rows + causal_offset >= cols)
                if window is not None:
                    allowed = allowed & (rows + causal_offset - cols < window)
            s = jnp.where(allowed, s, NEG_BIG)

        m_prev = m_ref[part.rows]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=columns))
        p = jnp.exp(s - column(m_new))
        if masked:
            # explicitly zero masked entries (when a whole tile is masked
            # m_new stays NEG_BIG and exp(s - m_new) would be 1)
            p = jnp.where(allowed, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_ref[part.rows] = m_new
        l_ref[part.rows] = l_ref[part.rows] * corr + jnp.sum(
            p, axis=-1, keepdims=columns)
        acc_ref[part.rows] = acc_ref[part.rows] * column(corr) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

    _tile_or_sub_blocks(
        _accumulate, visible, full, kl, qi, j, block_q=block_q,
        block_k=block_k, sub=sub, causal=causal, causal_offset=causal_offset,
        mask_q=has_lengths and mask_q, window=window)

    @pl.when(last)
    def _finish():
        o_ref[0] = (
            acc_ref[:] / column(jnp.maximum(l_ref[:], 1e-30))
        ).astype(o_ref.dtype)
        # per-row logsumexp of the (scaled, masked) logits — the backward
        # residual; NEG_BIG marks rows with no visible keys
        lse = jnp.where(
            l_ref[:] > 0.0, m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30)),
            NEG_BIG,
        )
        # the columns' lanes are equal: their max is the row's value
        lse_ref[0, 0] = jnp.max(lse, axis=-1) if columns else lse


def _pick_block(requested: int, t: int) -> int:
    """Largest block ≤ requested with tolerable padding waste.

    ``_pad_to`` rounds T up to a block multiple and padded rows are computed
    in full (only whole invisible tiles are skipped), so a 512 block at
    T=600 would do 70% garbage q-row work; halve the block until padding is
    under 1/8 of T (or the block reaches T / the 128-lane floor)."""
    b = min(requested, max(t, 1))
    while b > 128 and ((-t) % b) * 8 > t:
        b //= 2
    return b


# Mosaic's default scoped-VMEM limit on a v5e core is 16 MiB (later chips
# allow more); ``_working_set`` leaves out the compiler's own scratch and the
# (bq,) row statistics, about 1 MiB at the largest tiles (compiled for a
# described v5e: 21.0 MiB reported where the sum below says 20.0), and the
# (bq, 128) columns that hold the row statistics where masked tiles have
# sub-blocks (2 MiB at bq 1024 over both directions; the forward at the
# cells' shapes holds 11 - 12.5 MiB with them)
_VMEM_BUDGET = 15 * 2 ** 20
_LARGEST_TILE = 1024
# the one backward kernel holds dK and dV of a whole K/V head: it asks for
# ``_VMEM_LIMIT`` of the 128 MiB a v5e core has (``ops/ssd_kernel.py`` does
# the same), and a shape gets it while ``_working_set`` with its terms stays
# under ``_FUSED_VMEM_BUDGET``. That sum is on the safe side of what Mosaic
# allocates (compiled for a described v5e: the least limit that compiles is
# 36 MiB where the sum says 36.0 at q/k 192, v 128, T 8192; 24 for 27.0 at
# head size 128; 23 for 25.5 at 64), and sums of up to 45 MiB compile under
# the limit
_VMEM_LIMIT = 48 * 2 ** 20
_FUSED_VMEM_BUDGET = 44 * 2 ** 20


def _working_set(bq: int, bk: int, d: int, itemsize: int,
                 d_v: Optional[int] = None, tk: Optional[int] = None) -> int:
    """Bytes of VMEM the hungriest of the forward kernel and the backward
    pair holds at once: the float32 score and probability tiles, every
    operand and result tile twice (the pipeline fetches the next while this
    one computes), the float32 accumulators. ``d`` is the head size of q and
    k, ``d_v`` that of v and the output (``d`` where none is given).

    With ``tk``, the padded length of the key axis: what the ONE backward
    kernel holds instead, whose dK/dV accumulators and result blocks span
    that axis."""
    d_v = d if d_v is None else d_v
    scores = 2 * bq * bk * 4
    q_tile, k_tile = bq * d * itemsize, bk * d * itemsize
    o_tile, v_tile = bq * d_v * itemsize, bk * d_v * itemsize
    if tk is not None:
        # q do dq | k v | dk dv over the whole axis, float32 and as written;
        # VMEM rows are whole groups of 128 lanes, which at this size counts
        lanes = -(-d // 128) * 128 + -(-d_v // 128) * 128
        return scores + 2 * (2 * q_tile + o_tile + k_tile + v_tile) \
            + bq * d * 4 + tk * lanes * (4 + 2 * itemsize)
    fwd = scores + 2 * (q_tile + o_tile + k_tile + v_tile) + bq * d_v * 4
    # q do dq | k v
    dq = scores + 2 * (2 * q_tile + o_tile + k_tile + v_tile) + bq * d * 4
    # q do | k v dk dv
    dkv = scores + 2 * (q_tile + o_tile + 2 * k_tile + 2 * v_tile) \
        + bk * (d + d_v) * 4
    return max(fwd, dq, dkv)


def backward_form(tk: int, bq: int, bk: int, d: int, itemsize: int,
                  d_v: Optional[int] = None):
    """(whether a call of these shapes gets the one backward kernel, the
    bytes of its float32 dK/dV accumulator over the whole key axis). The one
    kernel builds each tile's ``p`` and ``dp`` once where the pair builds
    them twice; it is one algorithm for every caller, and only a key axis
    too long for its accumulator to fit VMEM keeps the pair (bf16, head
    size 128: the working set is 27 MiB at T 8192 and 43 at 16384, which fit,
    and 75 at 32768, which does not)."""
    tkp = -(-tk // bk) * bk
    acc = tkp * (d + (d if d_v is None else d_v)) * 4
    fits = _working_set(bq, bk, d, itemsize, d_v, tk=tkp) <= _FUSED_VMEM_BUDGET
    return fits, acc


def pick_tiles(tq: int, tk: int, d: int, itemsize: int,
               d_v: Optional[int] = None):
    """(block_q, block_k) of the kernels, from the shapes of a call
    (``d``: head size of q and k; ``d_v``: of v and the output, ``d`` where
    none is given).

    The largest tiles win: on a v5e 1024 x 1024 was the fastest forward and,
    but for 1.8 % in one row, the fastest forward + backward of the nine
    pairs of 256 / 512 / 1024, at T 1024 - 8192, head sizes 64 and 128, with
    and without a window or lengths; (512, 1024) came second; k tiles of
    2048 lost (``tools/flash_tile_table.py``; the table is in
    docs/performance.md). So each axis takes the largest tile whose padding
    ``_pick_block`` tolerates, the k axis 1024 only where the keys fill one
    (below that nothing was measured and it stays at 512's), and while
    ``_working_set`` is over ``_VMEM_BUDGET`` the larger of the two halves,
    the q tile first. The mask's geometry does not enter the tiles (under a
    window of 1024 a smaller q tile computes fewer masked pairs and still
    lost, forward and with the forward recomputed, for K/V is read again
    for each q tile); it enters inside a masked tile, whose sub-blocks
    ``sub_block`` sizes from the tiles and the mask."""
    bq = _pick_block(_LARGEST_TILE, tq)
    bk = _pick_block(
        _LARGEST_TILE if tk >= _LARGEST_TILE else _LARGEST_TILE // 2, tk)
    while (_working_set(bq, bk, d, itemsize, d_v) > _VMEM_BUDGET
           and max(bq, bk) > 128):
        if bq >= bk:
            bq //= 2
        else:
            bk //= 2
    return bq, bk


_SUB_BLOCK = 512


def sub_block(bq: int, bk: int, causal: bool) -> Optional[int]:
    """The size of the square sub-blocks that the kernels cut a masked tile
    into, from the tiles and the mask, or ``None`` where a masked tile is
    computed whole, as one product.

    Under a causal mask the masked tiles are the diagonal's and, with a
    window, the window's edge: there a sub-block wholly hidden is skipped
    and one wholly visible skips the mask. On a v5e, at tiles of 1024,
    sub-blocks of 512 were the fastest of 128 / 256 / 512 / whole in every
    shape timed, forward and forward + backward: under a window of 512
    12.36 against 18.13 ms, of 1024 14.76 against 16.55, full causal at
    head sizes 64, 128, 192 by 10 - 13 %; 256 and 128 lost to 512
    everywhere, a sub-block's products being too small for what each one
    costs (``tools/flash_tile_table.py``; the table is in
    docs/performance.md): so neither the head size nor the window enters.
    A tile no larger than a sub-block, or one that it does not divide (a
    whole-T tile of a ragged T), is computed whole."""
    if not causal or min(bq, bk) <= _SUB_BLOCK or bq % _SUB_BLOCK \
            or bk % _SUB_BLOCK:
        return None
    return _SUB_BLOCK


def _tile_geometry(tq: int, tk: int, bq: int, bk: int, causal: bool,
                   window: Optional[int], sub: Optional[int] = None):
    """(tiles a head's grid computes on, pairs in them / pairs the mask lets
    through, pairs in the products the kernels compute / pairs the mask lets
    through): the kernels' own tile classification (``_classify``) without
    per-sequence lengths, which only a run knows. With ``sub`` a masked
    tile computes only its sub-blocks that the mask does not wholly hide;
    without, the last two agree."""
    off = tk - tq
    nq, nk = -(-tq // bq), -(-tk // bk)

    def classify(b_q, b_k):
        # (visible, full) of every (b_q x b_k) block of the padded grid
        first_row = np.arange(nq * bq // b_q)[:, None] * b_q + off
        first_col = np.arange(nk * bk // b_k)[None, :] * b_k
        visible = np.broadcast_to(first_col < tk, (first_row.size,
                                                    first_col.size))
        full = first_col + b_k <= tk
        if causal:
            visible = visible & (first_row + b_q - 1 >= first_col)
            full = full & (first_row >= first_col + b_k - 1)
        if window is not None:
            visible = visible & (first_row - (first_col + b_k - 1) < window)
            full = full & (first_row + b_q - 1 - first_col < window)
        return visible, full

    rows = np.arange(tq) + off
    hi = np.full(tq, tk - 1)
    lo = np.zeros(tq, np.int64)
    if causal:
        hi = np.minimum(rows, hi)
    if window is not None:
        lo = np.maximum(rows - (window - 1), 0)
    visible = max(int(np.maximum(hi - lo + 1, 0).sum()), 1)
    visited, full = classify(bq, bk)
    tiles = int(visited.sum())
    computed = tiles * bq * bk
    if sub is not None:
        masked = np.kron(visited & ~full,
                         np.ones((bq // sub, bk // sub), bool))
        computed = int((visited & full).sum()) * bq * bk \
            + int((classify(sub, sub)[0] & masked).sum()) * sub * sub
    return tiles, tiles * bq * bk / visible, computed / visible


_tile_records: dict = {}  # shape and tile -> (when last traced, the record)
_tile_records_lock = threading.Lock()


def take_tile_records(since: float = 0.0) -> list:
    """The tile choices traced at or after ``since`` (a ``time.perf_counter``
    reading), one per distinct (Tq, Tk, d, d_v, dtype, causal, window), and forget
    them all: ``Telemetry`` writes those that the compiling call's own trace
    made into its ``compile`` record; what an earlier, unobserved trace left
    behind belongs to no record."""
    with _tile_records_lock:
        out = [record for at, record in _tile_records.values() if at >= since]
        _tile_records.clear()
    return out


def _resolve_tiles(q, k, v, causal: bool, window: Optional[int],
                   block_q: Optional[int], block_k: Optional[int]):
    """(block_q, block_k, whether the backward is the one kernel, the
    sub-block of a masked tile or ``None``): every choice that follows from
    a call's shapes, made and recorded here."""
    tq, tk, d, d_v = q.shape[2], k.shape[2], q.shape[3], v.shape[3]
    bq, bk = pick_tiles(tq, tk, d, q.dtype.itemsize, d_v)
    if block_q is not None:
        bq = _pick_block(block_q, tq)
    if block_k is not None:
        bk = _pick_block(block_k, tk)
    fused, acc = backward_form(tk, bq, bk, d, q.dtype.itemsize, d_v)
    sub = sub_block(bq, bk, causal)
    key = (tq, tk, d, d_v, q.dtype.name, causal, window, bq, bk)
    with _tile_records_lock:
        if key in _tile_records:
            record = _tile_records[key][1]
        else:
            tiles, waste, computed = _tile_geometry(tq, tk, bq, bk, causal,
                                                    window, sub)
            record = dict(
                tq=tq, tk=tk, d=d, dtype=q.dtype.name, causal=causal,
                window=window, block_q=bq, block_k=bk, visited_tiles=tiles,
                visited_over_visible=round(waste, 4), sub_block=sub,
                computed_over_visible=round(computed, 4),
                backward="fused" if fused else "pair",
                backward_acc_bytes=acc)
            if d_v != d:  # v and the output at a head size of their own
                record["d_v"] = d_v
        _tile_records[key] = (time.perf_counter(), record)
    return bq, bk, fused, sub


def _window_start(qi, block_q: int, block_k: int, causal_offset: int,
                  window: int):
    """First k tile that a q tile's window reaches (for the dK/dV kernel,
    with the roles swapped and ``window=1``: the first q tile at or below a
    k tile's diagonal)."""
    return jnp.maximum(qi * block_q + causal_offset - (window - 1), 0) // block_k


def _window_count(n_outer: int, block_o: int, block_i: int, offset: int,
                  reach: int, back: int, n_inner: int) -> int:
    """The most inner tiles that any outer tile's window touches: outer tile
    ``o`` covers positions ``[o * block_o + offset - back, (o + 1) * block_o
    - 1 + offset + reach]`` of the inner axis (k tiles of a q tile: ``back =
    window - 1``, ``reach = 0``; q tiles of a k tile: ``back = 0``, ``reach =
    window - 1``). Counted exactly, tile by tile, so that no grid step is
    spent on a tile that is never visible."""
    most = 1
    for o in range(n_outer):
        first = max(o * block_o + offset - back, 0) // block_i
        last = min(((o + 1) * block_o - 1 + offset + reach) // block_i,
                   n_inner - 1)
        most = max(most, last - first + 1)
    return most


def _window_tiles(visible, full, in_range, qi, j, block_q: int, block_k: int,
                  causal_offset: int, window: int):
    """Tile classification under a window: (row - col) spans
    [first row - last col, last row - first col] over a tile. ``in_range``
    is ``None`` for a sub-block, whose tile is in range."""
    visible = (visible if in_range is None else visible & in_range) & (
        qi * block_q + causal_offset - ((j + 1) * block_k - 1) < window)
    # a step past the last tile (its index was clamped) is neither
    full = (full if in_range is None else full & in_range) & (
        (qi + 1) * block_q - 1 + causal_offset - j * block_k < window)
    return visible, full


def _classify(kl, qi, j, *, block_q: int, block_k: int, causal: bool,
              causal_offset: int, mask_q: bool, window: Optional[int],
              nk_real: Optional[int]):
    """(visible, full) of the pair (q tile ``qi``, k tile ``j``), for the
    kernels whose outer axis is the q tile: whether any entry of it is
    unmasked, and whether every entry is. ``kl`` is the key horizon (a
    sequence's length where it has one), ``mask_q`` whether query rows past
    it are masked too. At a sub-block's size and indices (``nk_real``
    ``None``) the same arithmetic classifies a sub-block of a tile."""
    visible = j * block_k < kl
    full = (j + 1) * block_k <= kl
    if mask_q:
        # any/all of this q tile's rows inside the valid query horizon
        visible = visible & (qi * block_q + causal_offset < kl)
        full = full & ((qi + 1) * block_q - 1 + causal_offset < kl)
    if causal:
        visible = visible & (
            (qi + 1) * block_q - 1 + causal_offset >= j * block_k)
        full = full & (qi * block_q + causal_offset >= (j + 1) * block_k - 1)
    if window is not None:
        visible, full = _window_tiles(
            visible, full, None if nk_real is None else j < nk_real, qi, j,
            block_q, block_k, causal_offset, window)
    return visible, full


def _kv_row(group: int, h: int):
    """Grid row (batch * query head) -> row of the flattened K/V, whose
    ``h // group`` heads each serve ``group`` query heads."""
    if group == 1:
        return lambda b: b
    hkv = h // group
    return lambda b: (b // h) * hkv + (b % h) // group


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    t = x.shape[axis]
    pad = (-t) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _expand_lengths(lengths, n: int, h: int, tk: int):
    """(N,) per-sequence lengths -> (N*H,) int32 per-grid-row horizons; a
    ``None`` becomes the all-visible dummy (kernels compile it away)."""
    if lengths is None:
        return jnp.full((n * h,), tk, jnp.int32)
    return jnp.repeat(jnp.asarray(lengths, jnp.int32), h)


def _flash_fwd_impl(q, k, v, lengths, causal: bool, scale: Optional[float],
                    bq: int, bk: int, interpret: bool, mask_q: bool,
                    window: Optional[int] = None, sub: Optional[int] = None):
    """Returns (out (N,H,Tq,d_v), lse (N*H, Tq_padded)) — lse is the bwd
    residual. ``bq``/``bk`` are the resolved tiles and ``sub`` the masked
    tiles' sub-block (``_resolve_tiles``)."""
    n, h, tq, d = q.shape
    hkv, tk, d_v = k.shape[1], k.shape[2], v.shape[3]
    group = h // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    has_lengths = lengths is not None

    qf = _pad_to(q.reshape(n * h, tq, d), 1, bq)
    kf = _pad_to(k.reshape(n * hkv, tk, d), 1, bk)
    vf = _pad_to(v.reshape(n * hkv, tk, d_v), 1, bk)
    tqp, tkp = qf.shape[1], kf.shape[1]
    nk = tkp // bk
    lens = _expand_lengths(lengths, n, h, tk)
    kv_row = _kv_row(group, h)
    if window is None:
        nkv, extra = nk, {}
        kv_map = lambda b, i, j, lens: (kv_row(b), j, 0)  # noqa: E731
    else:
        # only the tiles a q tile's window reaches are in the grid
        nkv = _window_count(tqp // bq, bq, bk, tk - tq, 0, window - 1, nk)
        extra = dict(window=window, nk_real=nk)
        kv_map = lambda b, i, j, lens: (  # noqa: E731
            kv_row(b),
            jnp.minimum(_window_start(i, bq, bk, tk - tq, window) + j, nk - 1),
            0)
    stat = (bq,)  # the row statistics' scratch: columns with sub-blocks
    if sub is not None:
        extra["sub"] = sub
        stat = (bq, 128)

    out, lse = pallas_call(
        partial(_fwd_kernel, block_q=bq, block_k=bk, causal=causal,
                scale=scale, causal_offset=tk - tq, t_real_k=tk, nk=nkv,
                has_lengths=has_lengths, mask_q=mask_q, **extra),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n * h, tqp // bq, nkv),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, i, j, lens: (b, i, 0)),
                pl.BlockSpec((1, bk, d), kv_map),
                pl.BlockSpec((1, bk, d_v), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, d_v), lambda b, i, j, lens: (b, i, 0)),
                pl.BlockSpec((1, 1, bq), lambda b, i, j, lens: (b, 0, i)),
            ],
            scratch_shapes=[
                pltpu.VMEM(stat, jnp.float32),
                pltpu.VMEM(stat, jnp.float32),
                pltpu.VMEM((bq, d_v), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n * h, tqp, d_v), q.dtype),
            jax.ShapeDtypeStruct((n * h, 1, tqp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(lens, qf, kf, vf)
    return out[:, :tq].reshape(n, h, tq, d_v), lse


def _bwd_masked_p(q, k, lse, *, scale, masked, causal, causal_offset,
                  t_real_q, t_real_k, kl, mask_q, qi, ki, block_q, block_k,
                  window=None):
    """Rebuild the probability tile p = exp(s - lse); ``masked=False`` is the
    fast path for interior tiles where every entry is known visible (padded q
    rows are zeros with finite lse, so their p ≤ 1 and their contributions
    cancel against zero dO rows — no row mask needed). ``kl`` is the runtime
    key/query horizon (= t_real_k when no per-batch lengths)."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if not masked:
        return jnp.exp(s - (lse if lse.ndim == 2 else lse[:, None]))
    rows = qi * block_q + lax.broadcasted_iota(jnp.int32, (q.shape[0], k.shape[0]), 0)
    cols = ki * block_k + lax.broadcasted_iota(jnp.int32, (q.shape[0], k.shape[0]), 1)
    allowed = (cols < kl) & (rows < t_real_q)
    if mask_q:
        allowed = allowed & (rows + causal_offset < kl)
    if causal:
        allowed = allowed & (rows + causal_offset >= cols)
    if window is not None:
        allowed = allowed & (rows + causal_offset - cols < window)
    # masked/fully-masked entries: s and lse are both NEG_BIG-ish; clamp the
    # exponent so the unselected branch of the where never overflows
    expo = jnp.clip(s - (lse if lse.ndim == 2 else lse[:, None]), NEG_BIG,
                    0.0)
    return jnp.where(allowed, jnp.exp(expo), 0.0)


def _fill_columns(columns, lse_ref, delta_ref):
    """With sub-blocks (``columns``: two (block_q, 128) scratch refs), a q
    tile's logsumexp and delta turned into columns once, when the tile's
    first k step starts: each sub-block then reads its rows as a column and
    no row vector crosses between the lane and the sublane axis in it."""
    for col, ref in zip(columns, (lse_ref, delta_ref)):
        col[:] = jnp.broadcast_to(ref[0, 0][:, None], col.shape)


def _row_stats(columns, lse_ref, delta_ref, rows):
    """(the logsumexp of these rows, a function giving their delta as a
    column): from the columns where there are sub-blocks, else from the row
    vectors, read where they always were."""
    if columns:
        lse_cols, delta_cols = columns
        return lse_cols[rows, :1], lambda: delta_cols[rows, :1]
    return lse_ref[0, 0, rows], lambda: delta_ref[0, 0, rows][:, None]


def _dq_kernel(lens_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, *columns, block_q: int, block_k: int,
               causal: bool, scale: float, causal_offset: int, t_real_q: int,
               t_real_k: int, nk: int, has_lengths: bool, mask_q: bool,
               window: Optional[int] = None, nk_real: Optional[int] = None,
               sub: Optional[int] = None):
    """Grid (BH, num_q_blocks, num_k_blocks): k/v tiles stream through the
    inner dim while the dQ accumulator for the current q tile sits in VMEM.
    Under a ``window`` the inner dim holds the tiles inside it alone, and
    with ``sub`` a masked tile goes sub-block by sub-block, as in the
    forward kernel."""
    qi, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        _fill_columns(columns, lse_ref, delta_ref)

    last = j == nk - 1
    if window is not None:
        j = j + _window_start(qi, block_q, block_k, causal_offset, window)

    kl = jnp.minimum(lens_ref[pl.program_id(0)], t_real_k) if has_lengths \
        else t_real_k
    visible, full = _classify(
        kl, qi, j, block_q=block_q, block_k=block_k, causal=causal,
        causal_offset=causal_offset, mask_q=has_lengths and mask_q,
        window=window, nk_real=nk_real)

    def _accumulate(masked: bool, part: _Part):
        q = q_ref[0, part.rows]
        k = k_ref[0, part.cols]
        v = v_ref[0, part.cols]
        do = do_ref[0, part.rows]
        lse, delta = _row_stats(columns, lse_ref, delta_ref, part.rows)
        p = _bwd_masked_p(q, k, lse, scale=scale,
                          masked=masked, causal=causal,
                          causal_offset=causal_offset,
                          t_real_q=t_real_q, t_real_k=t_real_k, kl=kl,
                          mask_q=has_lengths and mask_q,
                          qi=part.qi, ki=part.kj, block_q=part.bq,
                          block_k=part.bk, window=window)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta()) * scale).astype(k.dtype)
        dq_acc[part.rows] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    _tile_or_sub_blocks(
        _accumulate, visible, full, kl, qi, j, block_q=block_q,
        block_k=block_k, sub=sub, causal=causal, causal_offset=causal_offset,
        mask_q=has_lengths and mask_q, window=window)

    @pl.when(last)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(lens_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                block_k: int, causal: bool, scale: float,
                causal_offset: int, t_real_q: int, t_real_k: int, nq: int,
                has_lengths: bool, mask_q: bool, window: Optional[int] = None,
                nq_real: Optional[int] = None, group: int = 1,
                sub: Optional[int] = None):
    """Grid (B*Hkv, num_k_blocks, group * num_q_blocks): q/do tiles stream
    through the inner dim; dK/dV accumulators for the current k tile sit in
    VMEM. With grouped heads the inner dim runs over the ``group`` query heads
    that read this K/V head, one after another, and their contributions sum
    in the accumulators. Under a ``window`` each head's stretch holds only
    the ``nq`` q tiles whose window reaches this k tile. With ``sub`` a
    masked tile goes sub-block by sub-block in the order the one kernel
    takes them."""
    ki, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    last = j == group * nq - 1
    if group > 1:
        j = j % nq
    if window is not None:
        j = j + _window_start(ki, block_k, block_q, -causal_offset, 1)

    lens_row = pl.program_id(0) * group if group > 1 else pl.program_id(0)
    kl = jnp.minimum(lens_ref[lens_row], t_real_k) if has_lengths \
        else t_real_k
    visible = j * block_q < t_real_q
    # full tiles: all k columns real and (under causal) the whole q tile past
    # the k tile's horizon; padded q rows need no mask (see _bwd_masked_p)
    full = (ki + 1) * block_k <= kl
    if has_lengths:
        # k tiles past the horizon produce zero dk/dv
        visible = visible & (ki * block_k < kl)
    if has_lengths and mask_q:
        # q tiles fully past the horizon contribute nothing either
        visible = visible & (j * block_q + causal_offset < kl)
        full = full & ((j + 1) * block_q - 1 + causal_offset < kl)
    if causal:
        visible = visible & (
            (j + 1) * block_q - 1 + causal_offset >= ki * block_k
        )
        full = full & (j * block_q + causal_offset >= (ki + 1) * block_k - 1)
    if window is not None:
        visible, full = _window_tiles(visible, full, j < nq_real, j, ki,
                                      block_q, block_k, causal_offset, window)

    def _accumulate(masked: bool, part: _Part):
        q = q_ref[0, part.rows]
        k = k_ref[0, part.cols]
        v = v_ref[0, part.cols]
        do = do_ref[0, part.rows]
        p = _bwd_masked_p(q, k, lse_ref[0, 0, part.rows], scale=scale,
                          masked=masked, causal=causal,
                          causal_offset=causal_offset,
                          t_real_q=t_real_q, t_real_k=t_real_k, kl=kl,
                          mask_q=has_lengths and mask_q,
                          qi=part.qi, ki=part.kj, block_q=part.bq,
                          block_k=part.bk, window=window)
        dv_acc[part.cols] += jnp.dot(
            p.astype(do.dtype).T, do, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0, part.rows][:, None])
              * scale).astype(q.dtype)
        dk_acc[part.cols] += jnp.dot(ds.T, q,
                                     preferred_element_type=jnp.float32)

    _tile_or_sub_blocks(
        _accumulate, visible, full, kl, j, ki, block_q=block_q,
        block_k=block_k, sub=sub, causal=causal, causal_offset=causal_offset,
        mask_q=has_lengths and mask_q, window=window)

    @pl.when(last)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_kernel(lens_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *columns,
                block_q: int, block_k: int, causal: bool, scale: float,
                causal_offset: int, t_real_q: int, t_real_k: int, nq: int,
                nk: int, has_lengths: bool, mask_q: bool,
                window: Optional[int] = None, nk_real: Optional[int] = None,
                group: int = 1, sub: Optional[int] = None):
    """The one backward kernel. Grid (B*Hkv, group * num_q_blocks,
    num_k_blocks), the dQ kernel's loop order: k/v tiles stream through the
    inner dim, and every visited tile builds ``p`` and ``dp`` once and feeds
    all three gradients from them. dQ accumulates per q tile, as in
    ``_dq_kernel``; dK and dV accumulate in float32 over the WHOLE key axis
    of this K/V head (``dk_acc`` (Tk, d), ``dv_acc`` (Tk, d_v)), the k
    tile's rows addressed by a tile-aligned dynamic slice, and are cast and
    written out once, after the last q tile of the last query head of the
    group. A k tile meets its q tiles in the order ``_dkv_kernel`` meets
    them (head by head, q tile by q tile), and with ``sub`` a masked tile's
    sub-blocks in the order ``_each_sub_block`` gives both, so the float32
    sums are the pair's."""
    gi, j = pl.program_id(1), pl.program_id(2)
    qi = gi % nq if group > 1 else gi

    @pl.when((gi == 0) & (j == 0))
    def _init_head():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        _fill_columns(columns, lse_ref, delta_ref)

    last = j == nk - 1
    if window is not None:
        j = j + _window_start(qi, block_q, block_k, causal_offset, window)

    lens_row = pl.program_id(0) * group if group > 1 else pl.program_id(0)
    kl = jnp.minimum(lens_ref[lens_row], t_real_k) if has_lengths \
        else t_real_k
    visible, full = _classify(
        kl, qi, j, block_q=block_q, block_k=block_k, causal=causal,
        causal_offset=causal_offset, mask_q=has_lengths and mask_q,
        window=window, nk_real=nk_real)

    def _accumulate(masked: bool, part: _Part):
        q = q_ref[0, part.rows]
        k = k_ref[0, part.cols]
        v = v_ref[0, part.cols]
        do = do_ref[0, part.rows]
        lse, delta = _row_stats(columns, lse_ref, delta_ref, part.rows)
        p = _bwd_masked_p(q, k, lse, scale=scale,
                          masked=masked, causal=causal,
                          causal_offset=causal_offset,
                          t_real_q=t_real_q, t_real_k=t_real_k, kl=kl,
                          mask_q=has_lengths and mask_q,
                          qi=part.qi, ki=part.kj, block_q=part.bq,
                          block_k=part.bk, window=window)
        # this part's key rows of the whole-axis accumulators
        rows = pl.ds(pl.multiple_of(part.kj * part.bk, part.bk), part.bk)
        dv_acc[rows, :] += jnp.dot(
            p.astype(do.dtype).T, do, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta()) * scale).astype(q.dtype)
        dk_acc[rows, :] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        dq_acc[part.rows] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    _tile_or_sub_blocks(
        _accumulate, visible, full, kl, qi, j, block_q=block_q,
        block_k=block_k, sub=sub, causal=causal, causal_offset=causal_offset,
        mask_q=has_lengths and mask_q, window=window)

    @pl.when(last)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    @pl.when(last & (gi == group * nq - 1))
    def _finish_head():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_impl(q, k, v, lengths, o, lse, g, causal: bool,
                    scale: Optional[float], bq: int, bk: int,
                    interpret: bool, mask_q: bool,
                    window: Optional[int], fused: bool,
                    sub: Optional[int] = None):
    """dQ, dK, dV off the forward's output and logsumexp. ``fused`` is
    ``_resolve_tiles``' choice from the shapes: the one kernel with its
    whole-axis dK/dV accumulator, or the pair where that does not fit;
    ``sub`` the masked tiles' sub-block, the forward's."""
    n, h, tq, d = q.shape
    hkv, tk, d_v = k.shape[1], k.shape[2], v.shape[3]
    group = h // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    has_lengths = lengths is not None

    qf = _pad_to(q.reshape(n * h, tq, d), 1, bq)
    kf = _pad_to(k.reshape(n * hkv, tk, d), 1, bk)
    vf = _pad_to(v.reshape(n * hkv, tk, d_v), 1, bk)
    dof = _pad_to(g.reshape(n * h, tq, d_v), 1, bq)  # zero-padded rows
    tqp, tkp = qf.shape[1], kf.shape[1]
    nq, nk = tqp // bq, tkp // bk
    lens = _expand_lengths(lengths, n, h, tk)
    off = tk - tq

    # delta_i = rowsum(dO_i * O_i): O(T d) work — jnp outside the grid
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = _pad_to(delta.reshape(n * h, 1, tq), 2, bq)

    common = dict(block_q=bq, block_k=bk, causal=causal, scale=scale,
                  causal_offset=off, t_real_q=tq, t_real_k=tk,
                  has_lengths=has_lengths, mask_q=mask_q)
    if window is None:
        nkv, nqv, dq_extra, dkv_extra = nk, nq, {}, {}
        k_of = lambda i, j: j  # noqa: E731  the k tile of a q tile's step j
        q_of = lambda i, j: j  # noqa: E731  dK/dV: the q tile of inner step j
    else:
        nkv = _window_count(nq, bq, bk, off, 0, window - 1, nk)
        nqv = _window_count(nk, bk, bq, -off, window - 1, 0, nq)
        dq_extra = dict(window=window, nk_real=nk)
        dkv_extra = dict(window=window, nq_real=nq)
        k_of = lambda i, j: jnp.minimum(  # noqa: E731
            _window_start(i, bq, bk, off, window) + j, nk - 1)
        q_of = lambda i, j: jnp.minimum(  # noqa: E731
            _window_start(i, bk, bq, -off, 1) + j, nq - 1)
    if group > 1:
        dkv_extra["group"] = group
    columns = []  # with sub-blocks: the q tile's lse and delta as columns
    if sub is not None:
        common["sub"] = sub
        columns = [pltpu.VMEM((bq, 128), jnp.float32)] * 2

    def unpadded(dq, dk, dv):
        return (dq[:, :tq].reshape(n, h, tq, d),
                dk[:, :tk].reshape(n, hkv, tk, d),
                dv[:, :tk].reshape(n, hkv, tk, d_v))

    if fused:
        # step i of the middle dim of K/V row b: query head i // nq of the
        # group, and its q tile i % nq
        if group == 1:
            head, tile = (lambda b, i: b), (lambda i: i)
        else:
            head = lambda b, i: b * group + i // nq  # noqa: E731
            tile = lambda i: i % nq  # noqa: E731
        q_map = lambda b, i, j, lens: (head(b, i), tile(i), 0)  # noqa: E731
        row_map = lambda b, i, j, lens: (head(b, i), 0, tile(i))  # noqa: E731
        kv_map = lambda b, i, j, lens: (b, k_of(tile(i), j), 0)  # noqa: E731
        whole = lambda b, i, j, lens: (b, 0, 0)  # noqa: E731
        return unpadded(*pallas_call(
            partial(_bwd_kernel, nq=nq, nk=nkv, group=group, **common,
                    **dq_extra),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(n * hkv, group * nq, nkv),
                in_specs=[
                    pl.BlockSpec((1, bq, d), q_map),
                    pl.BlockSpec((1, bk, d), kv_map),
                    pl.BlockSpec((1, bk, d_v), kv_map),
                    pl.BlockSpec((1, bq, d_v), q_map),
                    pl.BlockSpec((1, 1, bq), row_map),
                    pl.BlockSpec((1, 1, bq), row_map),
                ],
                out_specs=[
                    pl.BlockSpec((1, bq, d), q_map),
                    pl.BlockSpec((1, tkp, d), whole),
                    pl.BlockSpec((1, tkp, d_v), whole),
                ],
                scratch_shapes=[
                    pltpu.VMEM((bq, d), jnp.float32),
                    pltpu.VMEM((tkp, d), jnp.float32),
                    pltpu.VMEM((tkp, d_v), jnp.float32),
                ] + columns,
            ),
            out_shape=[
                jax.ShapeDtypeStruct((n * h, tqp, d), q.dtype),
                jax.ShapeDtypeStruct((n * hkv, tkp, d), k.dtype),
                jax.ShapeDtypeStruct((n * hkv, tkp, d_v), v.dtype),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=interpret,
            name="flash_bwd",
        )(lens, qf, kf, vf, dof, lse, delta))

    # the pair: a key axis too long for the one kernel's accumulator
    kv_row = _kv_row(group, h)
    kv_map = lambda b, i, j, lens: (kv_row(b), k_of(i, j), 0)  # noqa: E731
    dq = pallas_call(
        partial(_dq_kernel, nk=nkv, **common, **dq_extra),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n * h, nq, nkv),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, i, j, lens: (b, i, 0)),
                pl.BlockSpec((1, bk, d), kv_map),
                pl.BlockSpec((1, bk, d_v), kv_map),
                pl.BlockSpec((1, bq, d_v), lambda b, i, j, lens: (b, i, 0)),
                pl.BlockSpec((1, 1, bq), lambda b, i, j, lens: (b, 0, i)),
                pl.BlockSpec((1, 1, bq), lambda b, i, j, lens: (b, 0, i)),
            ],
            out_specs=pl.BlockSpec((1, bq, d),
                                   lambda b, i, j, lens: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)] + columns,
        ),
        out_shape=jax.ShapeDtypeStruct((n * h, tqp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(lens, qf, kf, vf, dof, lse, delta)

    # inner step j of K/V row b: query head j // nqv of the group, and the
    # j % nqv-th q tile that reaches k tile i
    if group == 1:
        q_row = lambda b, j: b  # noqa: E731
        q_tile = q_of
    else:
        q_row = lambda b, j: b * group + j // nqv  # noqa: E731
        q_tile = lambda i, j: q_of(i, j % nqv)  # noqa: E731
    q_map = lambda b, i, j, lens: (q_row(b, j), q_tile(i, j), 0)  # noqa: E731
    kv_tile = lambda b, i, j, lens: (b, i, 0)  # noqa: E731
    row_map = lambda b, i, j, lens: (q_row(b, j), 0, q_tile(i, j))  # noqa: E731
    dk, dv = pallas_call(
        partial(_dkv_kernel, nq=nqv, **common, **dkv_extra),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n * hkv, nk, group * nqv),
            in_specs=[
                pl.BlockSpec((1, bq, d), q_map),
                pl.BlockSpec((1, bk, d), kv_tile),
                pl.BlockSpec((1, bk, d_v), kv_tile),
                pl.BlockSpec((1, bq, d_v), q_map),
                pl.BlockSpec((1, 1, bq), row_map),
                pl.BlockSpec((1, 1, bq), row_map),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, d), kv_tile),
                pl.BlockSpec((1, bk, d_v), kv_tile),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d_v), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n * hkv, tkp, d), k.dtype),
            jax.ShapeDtypeStruct((n * hkv, tkp, d_v), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(lens, qf, kf, vf, dof, lse, delta)

    return unpadded(dq, dk, dv)


def _dense_reference(q, k, v, causal: bool, scale: Optional[float],
                     window: Optional[int] = None) -> jax.Array:
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if k.shape[1] != q.shape[1]:  # grouped heads: each K/V head, repeated
        group = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("nhqd,nhkd->nhqk", q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        rows = jnp.arange(tq)[:, None] + (tk - tq)
        cols = jnp.arange(tk)[None, :]
        mask = rows >= cols
        if window is not None:
            mask = mask & (rows - cols < window)
        s = jnp.where(mask, s, -jnp.inf)
        # rows with NO visible keys (Tq > Tk head rows): softmax over all -inf
        # is nan (and nan-poisons the vjp); the flash forward returns 0 there —
        # sanitize those rows BEFORE softmax, then zero them, so forward and
        # backward both agree with the kernel
        row_has = mask.any(-1)[None, None, :, None]
        s = jnp.where(row_has, s, 0.0)
        w = jnp.where(row_has, jax.nn.softmax(s, axis=-1), 0.0)
    else:
        w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nhqk,nhkd->nhqd", w.astype(q.dtype), v)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12))
def _flash_core(q, k, v, lengths, causal, scale, block_q, block_k, interpret,
                mask_q, window, fused, sub=None):
    out, _ = _flash_fwd_impl(q, k, v, lengths, causal, scale, block_q,
                             block_k, interpret, mask_q, window, sub)
    return out


def _fwd_rule(q, k, v, lengths, causal, scale, block_q, block_k, interpret,
              mask_q, window, fused, sub=None):
    out, lse = _flash_fwd_impl(q, k, v, lengths, causal, scale, block_q,
                               block_k, interpret, mask_q, window, sub)
    # what only a second run of the forward kernel could rebuild: under
    # nn.Remat these two are kept and the backward runs flash_fwd no more
    out, lse = keep(out, "flash_out"), keep(lse, "flash_lse")
    return out, (q, k, v, lengths, out, lse)


def _bwd_rule(causal, scale, block_q, block_k, interpret, mask_q, window,
              fused, sub, res, g):
    q, k, v, lengths, o, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, lengths, o, lse, g, causal, scale,
                                 block_q, block_k, interpret, mask_q, window,
                                 fused, sub)
    return dq, dk, dv, None


_flash_core.defvjp(_fwd_rule, _bwd_rule)


def flash_attention(q, k, v, causal: bool = False, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False,
                    lengths: Optional[jax.Array] = None,
                    mask_q: Optional[bool] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Exact attention over (N, heads, T, d) operands via the Pallas kernel.

    ``q`` and ``k`` share their head size ``d``; ``v`` may have another, and
    the output then has ``v``'s (latent attention: q/k heads of 192 = 128 +
    a rotary 64, v heads of 128). ``scale`` is ``1/sqrt(d)`` of q's where
    none is given. A head size need not be a multiple of the 128 lanes: the
    tiles span the whole head, and nothing is padded in HBM.

    ``causal`` applies the lower-triangular mask (aligned at the end for
    rectangular Tq != Tk). ``lengths`` (int (N,)) masks a PADDED batch:
    sequence n attends only keys ``< lengths[n]`` — so ragged text batches
    (the reference's padded-MiniBatch pipeline, ``$DL/dataset``) stay on
    the kernel path instead of falling back to dense.

    ``window`` (with ``causal``) is sliding-window attention: query i sees
    key j iff ``j <= i`` and ``i - j < window``. Tiles wholly outside the
    window are not in the grid at all, and the sub-blocks of a tile that
    the window wholly hides are skipped, so the work is near the window's.

    ``k`` and ``v`` may carry fewer heads than ``q`` (grouped-query
    attention): query head h reads K/V head ``h // (Hq / Hkv)``; nothing is
    repeated, and dK/dV sum over a group's query heads inside the kernel.

    ``mask_q`` controls whether QUERY rows past the horizon also produce
    zero output and leak no gradient (self-attention semantics, where
    queries and keys share ``lengths``). ``None`` keeps the shape
    heuristic (Tq == Tk → self-attention) for direct callers, but
    CROSS-attention with equal padded Tq/Tk must pass ``mask_q=False``
    explicitly — the heuristic would silently zero valid decoder rows
    (round-4 advisor finding); the in-framework call sites in
    ``bigdl_tpu.nn.attention`` always pass it explicitly. When masking
    rectangular queries the row position follows the aligned-at-end
    convention (row i ↔ global position ``i + Tk - Tq``), matching
    ``causal``. Composes with ``causal``.

    The (q, k) tile of the kernels follows the shapes (``pick_tiles``);
    ``block_q`` / ``block_k`` override it, for tests.

    ``interpret=True`` runs through the Pallas interpreter (for CPU
    tests). Differentiable: the backward is one Pallas kernel streaming
    tiles off the saved logsumexp, or the pair of them where the key axis is
    too long for its dK/dV accumulator; the shapes decide (module docstring,
    ``backward_form``).
    """
    if mask_q is None:
        mask_q = q.shape[2] == k.shape[2]
    if window is not None and not causal:
        raise ValueError("flash_attention: a window needs causal=True")
    if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(
            f"flash_attention: {q.shape[1]} query heads cannot share "
            f"{k.shape[1]} key / {v.shape[1]} value heads")
    if q.shape[3] != k.shape[3]:
        raise ValueError(
            f"flash_attention: q heads of {q.shape[3]} against k heads of "
            f"{k.shape[3]}; q and k share a head size, v may have its own")
    bq, bk, fused, sub = _resolve_tiles(q, k, v, causal, window, block_q,
                                        block_k)
    return _flash_core(q, k, v, lengths, causal, scale, bq, bk,
                       interpret, bool(mask_q), window, fused, sub)
