"""The state-space recurrence of a Mamba-2 layer in its chunked dual form
(Dao & Gu 2024, "Transformers are SSMs", arXiv:2405.21060, section 6).

Per head, with a scalar decay a token::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S is (P, N), S_0 = 0
    y_t = S_t C_t + D x_t

B and C come in ``groups`` B/C groups of ``state`` each, (n, T, groups,
state): head ``h`` of ``heads`` reads group ``h // (heads / groups)``, and
product 1 below is a group's (one group shared by all heads is Mamba-2's
default; a model with eight has eight ``C B^T`` a chunk).

Run token by token that is T sequential steps. ``ssd_scan`` cuts the record
into chunks of ``chunk`` tokens and computes the same values from four matrix
products and one short scan over the chunks' states. With ``a = dt A <= 0``
and ``cum`` its inclusive running sum INSIDE a chunk:

* inside a chunk, ``Y = ((C B^T) * L) (dt x)`` with ``L[t, s] = exp(cum_t -
  cum_s)`` for ``s <= t`` and 0 above the diagonal (products 1 and 2);
* what a chunk adds to the state, ``B^T (exp(cum_last - cum_s) dt x)``
  (product 3), carried from chunk to chunk by ``lax.scan``:
  ``S <- exp(cum_last) S + that``;
* what the state entering a chunk gives its tokens, ``exp(cum_t) (C S)``
  (product 4).

Every exponential is of a difference of running sums that is <= 0, never a
ratio of exponentials. Log decays, their sums and exponentials and the
carried state are float32; the four products take their operands in
``Engine``'s compute dtype and sum in float32, like every other product of
the model (``utils/precision``).

Two forms compute that, chosen from what the code can see and by no caller.
On the TPU backend, where the shapes tile (``ssd_kernel.heads_per_step``: a
chunk that is a multiple of 128, heads that fill whole groups of 128 lanes
inside one B/C group),
products 2, 3 and 4, the D term and every other pass over an array of the
tokens' size are four Pallas kernels (``ops/ssd_kernel.py``: the chunks'
states and the chunks' outputs, each forward and backward), in which ``L``
exists only in VMEM and ``x`` and ``y`` keep the model's (tokens, heads x
head_dim) layout; XLA keeps product 1, the running sums and the carry. Every
other shape and backend takes the XLA form: there ``L`` is (chunks, heads,
chunk, chunk), 537 MB in float32 for one record of 8192 tokens and 64 heads,
and autodiff would keep several arrays of its size, so product 2 runs over
the heads in groups (``lax.map``), each group recomputed in the backward pass
(``jax.checkpoint``): what is live is one group's ``L`` and what the backward
keeps is the group's inputs (``head_group``, from the shapes). What a compiled
step chose is in its telemetry ``compile`` record (``ssd_scans``: ``groups``
and the heads of one, ``kernel``, and the heads a grid step or the head
group).

``ssd_sequential`` is the recurrence as written, one token at a time: the
tests' and ``chip_smoke.py``'s yardstick, not a path of the model.
"""

from __future__ import annotations

import threading
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..utils import precision
from . import ssd_kernel

# one group's float32 L (records x chunks x group x chunk x chunk) stays under
# this: 8 of 64 heads at one record of 8192 tokens in chunks of 256
_GROUP_BYTES = 64 * 2**20

_scan_records: dict = {}  # shapes -> (when last traced, the record)
_scan_records_lock = threading.Lock()


def take_scan_records(since: float = 0.0) -> list:
    """The scans traced at or after ``since`` (a ``time.perf_counter``
    reading): tokens, chunk, chunks a record, heads, whether the kernels ran
    (``kernel``) and their heads a grid step (``heads_per_step``) or the XLA
    form's head group (``head_group``), the B/C ``groups`` and the heads of
    one (``group_heads``), one entry per distinct shape with the
    number of ``calls``. Forgets everything, as
    ``ops/flash_attention.take_tile_records`` does."""
    with _scan_records_lock:
        out = [record for at, record in _scan_records.values() if at >= since]
        _scan_records.clear()
    return out


def _record_scan(**record) -> None:
    key = tuple(sorted(record.items()))
    with _scan_records_lock:
        calls = _scan_records[key][1]["calls"] + 1 if key in _scan_records else 1
        _scan_records[key] = (time.perf_counter(), {**record, "calls": calls})


def head_group(records: int, chunks: int, heads: int, chunk: int,
               groups: int = 1) -> int:
    """Heads whose ``L`` is live at once, the same number from each of the
    ``groups`` B/C groups: the largest such divisor of ``heads`` whose float32
    ``L`` fits ``_GROUP_BYTES`` (at least one head a group)."""
    per_head = records * chunks * chunk * chunk * 4
    fit = max(_GROUP_BYTES // per_head, 1)
    return groups * max(g for g in range(1, heads // groups + 1)
                        if (heads // groups) % g == 0
                        and (g * groups <= fit or g == 1))


class ScanStats(NamedTuple):
    """What the chunked form's precision hangs on, for the step's counters."""
    log_decay_min: jax.Array    # most negative within-chunk running sum of dt A
    state_sq_sum: jax.Array     # sum of squares of the chunk-boundary states
    state_count: int            # how many numbers that is the sum over


def _dot(spec: str, a, b):
    """``einsum`` with operands in the compute dtype and a float32 sum."""
    return jnp.einsum(spec, precision.cast_compute(a), precision.cast_compute(b),
                      preferred_element_type=jnp.float32)


def _within_chunks(cb, cum, xdt, group: int):
    """Products 1's result ``cb`` (n, c, G, l, s), running sums ``cum``
    (n, c, G, h, l) and ``dt x`` (n, c, G, h, s, p), ``h`` the heads of one of
    the G B/C groups -> (n, c, G, h, l, p), ``group`` heads of each B/C group
    at a time."""
    n, c, bc, h, q = cum.shape
    p = xdt.shape[-1]
    seen = jnp.tril(jnp.ones((q, q), bool))

    @jax.checkpoint
    def one_group(cb, cum_g, xdt_g):         # (n, c, G, g, q), (n, c, G, g, q, p)
        seg = cum_g[..., :, None] - cum_g[..., None, :]
        decay = jnp.exp(jnp.where(seen, seg, -jnp.inf))   # L: 0 above the diagonal
        return _dot("ncbgls,ncbgsp->ncbglp", cb[:, :, :, None] * decay, xdt_g)

    if group == h:
        return one_group(cb, cum, xdt)
    # group-major for lax.map, and back
    cum = jnp.moveaxis(cum.reshape(n, c, bc, h // group, group, q), 3, 0)
    xdt = jnp.moveaxis(xdt.reshape(n, c, bc, h // group, group, q, p), 3, 0)
    out = jax.lax.map(lambda args: one_group(cb, *args), (cum, xdt))
    return jnp.moveaxis(out, 0, 3).reshape(n, c, bc, h, q, p)


def _carry(decay, added):
    """The chunks' states, one after another: ``S <- decay_c S + added_c``
    from ``S = 0`` over the chunks (axis 1 of both; ``decay`` broadcasts
    against a state) -> (the last state, the states entering the chunks)."""
    def step(state, chunk):
        by, add = chunk
        return state * by + add, state

    final, entering = jax.lax.scan(
        step, jnp.zeros(added.shape[:1] + added.shape[2:], jnp.float32),
        (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(added, 1, 0)))
    return final, jnp.moveaxis(entering, 0, 1)


def _chunks_xla(xdt, cum, b, c, cb, group: int):
    """The three products on arrays of the tokens' size and the carry between
    them in plain ``jax.numpy``: ``dt x`` (n, c q, h, p), running sums ``cum``
    (n, c, h, q), b and c (n, c, G, q, s), ``cb`` product 1's result (n, c, G,
    q, q), ``group`` of the h heads at a time -> (y without the D term (n,
    c q, h, p), the states entering the chunks, the last state). Inside, the
    heads are (G, h / G): each B/C group's own."""
    n, nc, h, q = cum.shape
    bc, s = b.shape[2], b.shape[-1]
    p = xdt.shape[-1]
    xdt = xdt.reshape(n, nc, q, bc, h // bc, p).transpose(0, 1, 3, 4, 2, 5)
    cum = cum.reshape(n, nc, bc, h // bc, q)
    y = _within_chunks(cb, cum, xdt, group // bc)                 # product 2
    last = cum[..., -1:]
    added = _dot("ncbqs,ncbhqp->ncbhps", b,
                 xdt * jnp.exp(last - cum)[..., None])
    final, entering = _carry(jnp.exp(last)[..., None], added)  # (n, c, G, h/G, p, s)
    y = y + _dot("ncbqs,ncbhps->ncbhqp", c, entering) * jnp.exp(cum)[..., None]
    y = y.reshape(n, nc, h, q, p).transpose(0, 1, 3, 2, 4)
    return (y.reshape(n, nc * q, h, p), entering.reshape(n, nc, h, p, s),
            final.reshape(n, h, p, s))


def _chunks_kernels(x, dt, cum, b, c, cb, d, per_step: int, interpret: bool):
    """The same from ``ops/ssd_kernel``'s two operations, with x (n, c q, h,
    p) and dt (n, c q, h) in place of ``dt x``, and the D term in y. Nothing
    of the tokens' size leaves the model's (tokens, heads x head_dim) layout
    or is touched by XLA; what is one number a head and token goes in as rows
    of (heads, chunk)."""
    n, nc, h, q = cum.shape
    p = x.shape[-1]
    x = x.reshape(n, nc * q, h * p)
    dt = dt.reshape(n, nc, q, h).transpose(0, 1, 3, 2)            # (n, c, h, q)
    last = cum[..., -1:]
    added = ssd_kernel.chunk_states(x, dt * jnp.exp(last - cum), b,
                                    per_step, interpret)          # (n, c, s, h p)
    decay = jnp.repeat(jnp.exp(last[..., 0]), p, axis=-1)         # (n, c, h p)
    final, entering = _carry(decay[:, :, None], added)
    y = ssd_kernel.chunk_outputs(
        cb, dt, cum, x, c, entering, jnp.repeat(d, p)[None], per_step, interpret)
    return y.reshape(n, nc * q, h, p), entering, final


def ssd_scan(x, dt, a, b, c, d, chunk: int, interpret: bool = False):
    """x (n, T, H, P); dt (n, T, H) > 0 (after softplus); a (H,) < 0; b and c
    (n, T, G, N) in G B/C groups, head h reading group ``h // (H / G)`` (or
    (n, T, N): one group shared by all heads); d (H,) -> (y (n, T, H, P)
    float32, ``ScanStats``). ``chunk`` is the model's (``mamba_chunk_size``);
    a record shorter than one chunk is one chunk, and a ragged last chunk is
    padded with tokens that leave the state as it is (dt = 0). On the TPU
    backend, where the shapes tile (``ssd_kernel.heads_per_step``), the work
    is ``ops/ssd_kernel``'s; ``interpret=True`` runs those kernels through
    the Pallas interpreter wherever the shapes tile: the CPU tests' way in."""
    n, t, h, p = x.shape
    if b.ndim == 3:
        b, c = b[:, :, None], c[:, :, None]
    groups, s = b.shape[2:]
    if h % groups:
        raise ValueError(f"ssd_scan: {h} heads do not split into {groups} "
                         "B/C groups")
    q = min(chunk, t)
    pad = -t % q
    x32, dt, d = x.astype(jnp.float32), dt.astype(jnp.float32), d.astype(jnp.float32)
    x = x32                                  # padded below; x32 keeps T tokens
    if pad:
        widen = lambda v: jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))  # noqa: E731
        x, dt, b, c = widen(x), widen(dt), widen(b), widen(c)
    nc = (t + pad) // q
    shape = dict(records=n, tokens=t, chunk=q, chunks=nc, heads=h, head_dim=p,
                 state=s, groups=groups, group_heads=h // groups)

    # (n, c, h, q): inclusive running sums of the log decay inside a chunk
    cum = jnp.cumsum((dt * a.astype(jnp.float32)).reshape(n, nc, q, h)
                     .transpose(0, 1, 3, 2), axis=-1)
    # (n, c, G, q, s): a B/C group's chunk is one block
    b = b.reshape(n, nc, q, groups, s).transpose(0, 1, 3, 2, 4)
    c = c.reshape(n, nc, q, groups, s).transpose(0, 1, 3, 2, 4)
    cb = _dot("ncgls,ncgts->ncglt", c, b)                         # product 1
    per_step = None
    if interpret or jax.default_backend() == "tpu":
        per_step = ssd_kernel.heads_per_step(
            h, p, q, s, precision.compute_dtype().itemsize, groups)
    if per_step:
        _record_scan(**shape, kernel=True, heads_per_step=per_step)
        y, entering, final = _chunks_kernels(x, dt, cum, b, c, cb, d,
                                             per_step, interpret)
        y = y[:, :t]
    else:
        group = head_group(n, nc, h, q, groups)
        _record_scan(**shape, kernel=False, head_group=group)
        y, entering, final = _chunks_xla(x * dt[..., None], cum, b, c, cb, group)
        y = y[:, :t] + d[:, None] * x32
    # the states at the chunks' ends: those that entered a later chunk (the
    # first is the zero state) and the last one
    stats = ScanStats(
        jax.lax.stop_gradient(jnp.min(cum)),
        jax.lax.stop_gradient(jnp.sum(entering * entering)
                              + jnp.sum(final * final)),
        nc * n * h * p * s)
    return y, stats


def ssd_sequential(x, dt, a, b, c, d, segment=None):
    """The recurrence one token at a time, in the inputs' own precision:
    same arguments as ``ssd_scan`` without the chunk (b and c in their G B/C
    groups or as one), -> y (n, T, H, P).
    With ``segment`` (a divisor of T) the tokens run in segments that the
    backward pass recomputes, so that its stored states are one segment's
    and not the record's (17 GB at 8192 tokens of a 64 x 64 x 128 state)."""
    if b.ndim == 3:
        b, c = b[:, :, None], c[:, :, None]
    per_group = x.shape[2] // b.shape[2]

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs               # (H, P), (H,), (G, N), (G, N)
        b_t, c_t = (jnp.repeat(v, per_group, axis=0) for v in (b_t, c_t))
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t) + d[:, None] * x_t

    def one_record(x, dt, b, c):
        zero = jnp.zeros(x.shape[1:] + (b.shape[-1],), x.dtype)
        if segment is None:
            return jax.lax.scan(token, zero, (x, dt, b, c))[1]
        cut = lambda v: v.reshape((-1, segment) + v.shape[1:])  # noqa: E731
        y = jax.lax.scan(
            jax.checkpoint(lambda state, inputs: jax.lax.scan(
                token, state, inputs)),
            zero, (cut(x), cut(dt), cut(b), cut(c)))[1]
        return y.reshape(x.shape)

    return jax.vmap(one_record)(x, dt, b, c)
