"""The chunked state-space scan's work on arrays of the tokens' size
(``ops/ssd.py``) as Pallas TPU kernels, forward and backward. ``x``, ``y`` and
their gradients stay in the model's own (tokens, heads x head_dim) layout and
cross HBM once a kernel; the decay matrix ``L[t, s] = exp(cum_t - cum_s)``
exists only in VMEM.

Two operations, each a ``jax.custom_vjp`` of one forward and one backward
kernel, with the short scan over the chunks' states between them (XLA's)::

    chunk_states:  added_c = B_c^T (w x)                   w = dt exp(cum_last - cum)
    chunk_outputs: y = ((C B^T) * L) (dt x) + exp(cum) (C S_c) + D x

One grid step takes one chunk and ``heads_per_step`` heads: a block of
(chunk, heads_per_step x head_dim), cut into lane groups of 128 (two heads of
64). B and C come in ``groups`` B/C groups, (n, chunks, groups, chunk, state),
head ``h`` reading group ``h // (heads / groups)``: a step's heads lie inside
one group, and its B, C and ``C B^T`` blocks are that group's (the index maps
divide the head step by the steps a group). What is summed over heads (dB, dC,
d(C B^T)) is summed over the head steps of ONE group: zeroed at the group's
first step, written back when the next group's block takes its place. What is one number a head and token (dt, the running sums, w) arrives as
rows of (heads, chunk) and is spread over the head's lanes inside the kernel:
XLA has no cheap way to do that to an array whose lanes hold several heads.
A chunk is cut into 128 x 128 blocks, of which only those at or below the
diagonal are computed (three of four at chunk 256); the mask is applied on the
diagonal blocks alone, below them every difference of running sums is <= 0
already. Per head and block: the float32 tile of ``L``, times the ``C B^T``
tile, rounded ONCE to the operands' dtype, into the matrix unit with a
float32 sum. The heads of a lane group share products of 128 lanes (the matrix
unit's width either way) and each keeps its own lanes of the result.

The backward kernels rebuild the same tiles from the same running sums,
transposed (rows ``s``, lanes ``t``) so that no product transposes a tile.
With ``M = (C B^T) * L`` and ``dM = dY (dt x)^T`` (never written out)::

    d(dt x)  = M^T dY
    d(C B^T) = sum over heads of dM * L      (heads: the sequential grid axis)
    d cum_t  = sum_s (dM * M)[t, s] - sum_s (dM * M)[s, t]  + exp(cum_t) dY_t . (C S_c)_t

The two sums of the last line are taken of ONE float32 tile, along its rows
and along its columns, so that they cancel to rounding as autodiff's do (the
same sums as ``sum_p dY Y`` and ``sum_p (dt x) d(dt x)``, which need no tile
but round ``dY`` differently and lose the gradient of ``A`` to cancellation).
Every product takes its operands in the compute dtype (``utils/precision``)
and sums in float32, forward and backward; everything else is float32. The
residuals are the operations' own inputs.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import precision
from ..utils.compat import pallas_call

_BLOCK = 128    # the matrix unit's width and a vector register's lanes
# what a grid step's blocks may hold, each twice (the pipeline fetches the
# next step's while this one computes); the compiler's own scratch for the
# float32 tiles comes on top, so the kernels ask for ``_VMEM_LIMIT`` of the
# 128 MiB a v5e core has, not its default 16 MiB
_VMEM_BUDGET = 12 * 2 ** 20
_VMEM_LIMIT = 48 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))      # a b^T
_UNROLL = 4     # lane groups a turn of a kernel's loop


def _working_set(per_step: int, head_dim: int, chunk: int, state: int,
                 itemsize: int) -> int:
    """Bytes of ``chunk_outputs``' backward blocks (the hungriest kernel),
    each twice: x, dY and dx float32; the entering state and its gradient;
    ``C B^T`` and its gradient; C, its transpose and its gradient; the rows
    of dt and the running sums and theirs."""
    wide = per_step * head_dim
    return 2 * (3 * chunk * wide * 4 + state * wide * (itemsize + 4)
                + 2 * chunk * chunk * 4 + chunk * state * (2 * itemsize + 4)
                + 4 * per_step * chunk * 4 + 2 * wide * 4)


def heads_per_step(heads: int, head_dim: int, chunk: int, state: int,
                   itemsize: int, groups: int = 1) -> Optional[int]:
    """Heads a grid step takes, from the shapes: the most whose blocks fit
    ``_VMEM_BUDGET`` (each step costs ~0.35 us whatever it holds), among the
    divisors of the heads of one B/C group (``heads / groups``: a step reads
    one group's B and C) that tile: the step's heads fill whole groups of 128
    lanes and, as rows of (heads, chunk), whole groups of 8 sublanes (or are
    all the heads). None where nothing tiles: the chunk is not a multiple of
    128, the state not of 8, the head size neither divides 128 nor is a
    multiple of it, the groups do not divide the heads. The caller then takes
    the XLA form."""
    if chunk % _BLOCK or state % 8 or (_BLOCK % head_dim and head_dim % _BLOCK) \
            or heads % groups:
        return None
    fits = [g for g in range(1, heads // groups + 1)
            if (heads // groups) % g == 0 and (g * head_dim) % _BLOCK == 0
            and (g % 8 == 0 or g == heads)
            and _working_set(g, head_dim, chunk, state, itemsize) <= _VMEM_BUDGET]
    return max(fits, default=None)


def _blocks(n: int):
    return [slice(i * _BLOCK, (i + 1) * _BLOCK) for i in range(n)]


def _decay(ahead, behind, diagonal: bool, lower: bool = True):
    """``L``'s (128, 128) block, or its transpose, from the running sums of the
    tokens ``t`` (``ahead``) and ``s <= t`` (``behind``), one a (128, 1) column
    and the other a (1, 128) row: the exponential of a difference that is
    <= 0, never a ratio of exponentials. On the diagonal the pairs with
    ``s > t`` are masked: below it (``lower``) where rows are ``t``, above it
    where rows are ``s``."""
    seg = ahead - behind
    if diagonal:
        row = lax.broadcasted_iota(jnp.int32, seg.shape, 0)
        lane = lax.broadcasted_iota(jnp.int32, seg.shape, 1)
        seg = jnp.where(row >= lane if lower else row <= lane, seg, -jnp.inf)
    return jnp.exp(seg)


class _Group:
    """The ``j``-th lane group of a step's block (``j`` a loop's counter):
    128 lanes (of one head where a head is wider), the heads in it, and the
    moves between a number a head and the head's lanes."""

    def __init__(self, j, head_dim: int):
        self.wide, self.head_dim = max(head_dim, _BLOCK), head_dim
        self.per = self.wide // head_dim
        self.lanes = pl.ds(pl.multiple_of(j * self.wide, _BLOCK), self.wide)
        self.heads = [j * self.per + i for i in range(self.per)]

    def head_of(self, tokens: int):
        """(tokens, lanes): which of the group's heads a lane belongs to (made
        at the size asked for: Mosaic cannot slice an iota's rows)."""
        return lax.broadcasted_iota(
            jnp.int32, (tokens, self.wide), 1) // self.head_dim

    def pick(self, parts, tokens: int):
        """Each head's own lanes of its array in ``parts``, (tokens, lanes)."""
        out, head_of = parts[0], self.head_of(tokens)
        for i in range(1, self.per):
            out = jnp.where(head_of == i, parts[i], out)
        return out

    def spread(self, rows_ref, fn=lambda v: v):
        """(tokens, lanes): ``fn`` of each head's row of ``rows_ref`` (heads,
        tokens), down the tokens and across the head's lanes."""
        return self.pick([fn(rows_ref[h])[:, None] for h in self.heads],
                         rows_ref.shape[1])

    def sums(self, values):
        """A head's lanes of ``values`` (tokens, lanes) summed: (tokens,) for
        each head of the group."""
        if self.per == 1:
            return [jnp.sum(values, axis=1)]
        head_of = self.head_of(values.shape[0])
        return [jnp.sum(jnp.where(head_of == i, values, 0.0), axis=1)
                for i in range(self.per)]


def _for_groups(width: int, head_dim: int, body) -> None:
    """``body(group)`` for each lane group of a block ``width`` lanes wide: a
    loop of ``_UNROLL`` groups a turn, not ``width // 128`` copies of the body
    in the kernel (the scheduler overlaps the groups of a turn; a turn apiece
    cost 0.9 ms a layer, every group unrolled 10 s more of Mosaic's compile)."""
    groups = width // max(head_dim, _BLOCK)
    turn = max(k for k in range(1, _UNROLL + 1) if groups % k == 0)

    def step(j, carry):
        for k in range(turn):
            body(_Group(j * turn + k, head_dim))
        return carry

    lax.fori_loop(0, groups // turn, step, 0)


# ------------------------------------------------------------ the chunks' states

def _states_kernel(x_ref, w_ref, bt_ref, added_ref, *, head_dim: int):
    """x_ref (q, lanes) float32; w_ref (heads a step, q) float32; bt_ref
    (state, q) in the operands' dtype; added_ref (state, lanes) float32."""
    def group(g):
        xw = (x_ref[:, g.lanes] * g.spread(w_ref)).astype(bt_ref.dtype)
        added_ref[:, g.lanes] = jnp.dot(bt_ref[...], xw,
                                        preferred_element_type=jnp.float32)

    _for_groups(x_ref.shape[1], head_dim, group)


def _states_bwd_kernel(x_ref, w_ref, b_ref, da_ref, dx_ref, dw_ref, db_ref, *,
                       head_dim: int, steps: int):
    """b_ref (q, state) in the operands' dtype; da_ref (state, lanes) float32;
    dx_ref like x_ref, dw_ref like w_ref, db_ref (q, state) float32 summed
    over the ``steps`` head steps of its B/C group."""
    @pl.when(pl.program_id(2) % steps == 0)
    def _first_heads():
        db_ref[...] = jnp.zeros_like(db_ref)

    def group(g):
        x, w = x_ref[:, g.lanes], g.spread(w_ref)
        da = da_ref[:, g.lanes].astype(b_ref.dtype)
        dxw = jnp.dot(b_ref[...], da, preferred_element_type=jnp.float32)
        dx_ref[:, g.lanes] = dxw * w
        for h, dw in zip(g.heads, g.sums(dxw * x)):
            dw_ref[h] = dw
        db_ref[...] += lax.dot_general((x * w).astype(b_ref.dtype), da, _NT,
                                       preferred_element_type=jnp.float32)

    _for_groups(x_ref.shape[1], head_dim, group)


# ----------------------------------------------------------- the chunks' outputs

def _outputs_kernel(cb_ref, dt_ref, cum_ref, x_ref, c_ref, s_ref, d_ref, y_ref,
                    *, head_dim: int):
    """cb_ref (q, q) float32; dt_ref and cum_ref (heads a step, q) float32;
    x_ref and y_ref (q, lanes) float32; c_ref (q, state) and s_ref (state,
    lanes) in the operands' dtype; d_ref (1, lanes) float32."""
    q, width = x_ref.shape
    blocks = _blocks(q // _BLOCK)
    dtype = c_ref.dtype

    def group(g):
        x = x_ref[:, g.lanes]
        xdt = (x * g.spread(dt_ref)).astype(dtype)
        rest = g.spread(cum_ref, jnp.exp) * jnp.dot(
            c_ref[...], s_ref[:, g.lanes], preferred_element_type=jnp.float32
        ) + d_ref[:, g.lanes] * x
        cums = [cum_ref[h] for h in g.heads]
        for bi, rows in enumerate(blocks):
            parts = []
            for cum in cums:
                col = cum[rows][:, None]
                m = jnp.concatenate(
                    [cb_ref[rows, cols] * _decay(col, cum[cols][None, :], bi == bj)
                     for bj, cols in enumerate(blocks[:bi + 1])], axis=1)
                parts.append(jnp.dot(m.astype(dtype), xdt[:(bi + 1) * _BLOCK],
                                     preferred_element_type=jnp.float32))
            y_ref[rows, g.lanes] = g.pick(parts, _BLOCK) + rest[rows]

    _for_groups(width, head_dim, group)


def _outputs_bwd_kernel(cbt_ref, dt_ref, cum_ref, x_ref, c_ref, ct_ref, s_ref,
                        d_ref, dy_ref, dx_ref, ddt_ref, dcum_ref, dcbt_ref,
                        dc_ref, ds_ref, dd_ref, *, head_dim: int, steps: int):
    """The forward's tiles transposed, rows ``s`` and lanes ``t``, so that no
    product transposes a tile: cbt_ref (q, q) is ``(C B^T)^T``, ct_ref (state,
    q) is ``C^T``. Gradients like what they are of, all float32; dcbt_ref and
    dc_ref summed over the ``steps`` head steps of their B/C group; dd_ref (1,
    lanes): ``dY x`` summed over the chunk's tokens."""
    q, width = x_ref.shape
    blocks = _blocks(q // _BLOCK)
    dtype = c_ref.dtype

    @pl.when(pl.program_id(2) % steps == 0)
    def _first_heads():
        dcbt_ref[...] = jnp.zeros_like(dcbt_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    def group(g):
        x, dy = x_ref[:, g.lanes], dy_ref[:, g.lanes]
        dt = g.spread(dt_ref)
        xdt, dyb = (x * dt).astype(dtype), dy.astype(dtype)
        dd_ref[:, g.lanes] = jnp.sum(dy * x, axis=0, keepdims=True)

        # y's part from the state entering the chunk: exp(cum) (C S)
        s = s_ref[:, g.lanes]
        scaled = (dy * g.spread(cum_ref, jnp.exp)).astype(dtype)
        ds_ref[:, g.lanes] = jnp.dot(ct_ref[...], scaled,
                                     preferred_element_type=jnp.float32)
        dc_ref[...] += lax.dot_general(scaled, s, _NT,
                                       preferred_element_type=jnp.float32)
        from_state = g.sums(dy * jnp.dot(c_ref[...], s,
                                         preferred_element_type=jnp.float32))

        # y's part from inside the chunk: M (dt x)
        cums = [cum_ref[h] for h in g.heads]
        # by head and block of tokens, (1, 128) pieces of the rows written at
        # the end (a row of a loop's head takes whole stores only)
        d_dt = [[] for _ in g.heads]
        d_cum = [[None] * len(blocks) for _ in g.heads]

        def add(i, b, piece):
            d_cum[i][b] = piece if d_cum[i][b] is None else d_cum[i][b] + piece

        for bj, rows in enumerate(blocks):              # rows: the tokens s
            parts = []
            for i, cum in enumerate(cums):
                xdt_head = xdt[rows] if g.per == 1 else jnp.where(
                    g.head_of(_BLOCK) == i, xdt[rows], jnp.zeros_like(xdt[rows]))
                col = cum[rows][:, None]
                decay = jnp.concatenate(            # the tokens t >= s: bi >= bj
                    [_decay(cum[cols][None, :], col, bi == bj, lower=False)
                     for bi, cols in list(enumerate(blocks))[bj:]], axis=1)
                m = cbt_ref[rows, bj * _BLOCK:] * decay
                parts.append(jnp.dot(m.astype(dtype), dyb[bj * _BLOCK:],
                                     preferred_element_type=jnp.float32))
                dm = lax.dot_general(xdt_head, dyb[bj * _BLOCK:], _NT,
                                     preferred_element_type=jnp.float32)
                dcbt_ref[rows, bj * _BLOCK:] += dm * decay
                # both sums of the one float32 tile: they cancel to rounding
                dmm = dm * m
                ahead = jnp.sum(dmm, axis=0, keepdims=True)   # over s, for t >= s
                for bi in range(bj, len(blocks)):
                    add(i, bi, ahead[:, blocks[bi - bj]])
                add(i, bj, -jnp.sum(dmm, axis=1)[None, :])
            dxdt = g.pick(parts, _BLOCK)
            dx_ref[rows, g.lanes] = dxdt * dt[rows] + d_ref[:, g.lanes] * dy[rows]
            for i, ddt in enumerate(g.sums(dxdt * x[rows])):
                d_dt[i].append(ddt[None, :])
        for i, (h, cum) in enumerate(zip(g.heads, cums)):
            dcum_ref[pl.ds(h, 1), :] = jnp.concatenate(d_cum[i], axis=1) \
                + (jnp.exp(cum) * from_state[i])[None, :]
            ddt_ref[pl.ds(h, 1), :] = jnp.concatenate(d_dt[i], axis=1)

    _for_groups(width, head_dim, group)


# ------------------------------------------------------------------- the calls

def _layouts(n, c, q, h, p, s, groups, per_step):
    """Each kind of array's (whole shape, block, index map) over the grid
    (records, chunks, head steps), for n records, c chunks of q tokens, h
    heads of p, state s, ``groups`` B/C groups. Arrays with no head axis keep
    one block for every head step of a B/C group."""
    wide = per_step * p
    steps = h // groups // per_step     # head steps a B/C group
    of_group = lambda r, k, g: (r, k, g // steps, 0, 0)  # noqa: E731
    return dict(
        tokens=((n, c * q, h * p), (None, q, wide), lambda r, k, g: (r, k, g)),
        rows=((n, c, h, q), (None, None, per_step, q), lambda r, k, g: (r, k, g, 0)),
        square=((n, c, groups, q, q), (None, None, None, q, q), of_group),
        by_state=((n, c, groups, q, s), (None, None, None, q, s), of_group),
        state_by=((n, c, groups, s, q), (None, None, None, s, q), of_group),
        states=((n, c, s, h * p), (None, None, s, wide), lambda r, k, g: (r, k, 0, g)),
        lane_row=((1, h * p), (1, wide), lambda r, k, g: (0, g)),
        chunk_row=((n, c, 1, h * p), (None, None, 1, wide), lambda r, k, g: (r, k, 0, g)))


# name -> (kernel, its inputs' kinds, its outputs' kinds (all float32), whether
# an output is summed over a B/C group's head steps)
_KERNELS = {
    "ssd_states_fwd": (_states_kernel, ("tokens", "rows", "state_by"),
                       ("states",), False),
    "ssd_states_bwd": (_states_bwd_kernel,
                       ("tokens", "rows", "by_state", "states"),
                       ("tokens", "rows", "by_state"), True),
    "ssd_outputs_fwd": (_outputs_kernel,
                        ("square", "rows", "rows", "tokens", "by_state",
                         "states", "lane_row"), ("tokens",), False),
    "ssd_outputs_bwd": (_outputs_bwd_kernel,
                        ("square", "rows", "rows", "tokens", "by_state",
                         "state_by", "states", "lane_row", "tokens"),
                        ("tokens", "rows", "rows", "square", "by_state",
                         "states", "chunk_row"), True),
}


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _run(name: str, dims, per_step: int, interpret: bool, *args):
    """The kernel ``name`` over the grid of ``dims`` = (n, c, q, h, p, s,
    groups).
    Jitted, so that a model's layers share one trace and one lowering of each
    kernel: traced anew at every call, the four cost a step of nine layers
    under ``nn.Remat`` 20 s of set-up, each time it is lowered."""
    kernel, ins, outs, sequential_heads = _KERNELS[name]
    n, c, q, h, p, s, groups = dims
    kinds = _layouts(*dims, per_step)
    sizes = dict(head_dim=p)
    if sequential_heads:
        sizes["steps"] = h // groups // per_step
    return pallas_call(
        partial(kernel, **sizes), grid=(n, c, h // per_step),
        in_specs=[pl.BlockSpec(*kinds[k][1:]) for k in ins],
        out_specs=[pl.BlockSpec(*kinds[k][1:]) for k in outs],
        out_shape=[jax.ShapeDtypeStruct(kinds[k][0], jnp.float32) for k in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel",
                "arbitrary" if sequential_heads else "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)(*args)


def _dims(rows, x, by_state):
    """(n, c, q, h, p, s, groups) from an array of each kind."""
    n, c, h, q = rows.shape
    return n, c, q, h, x.shape[-1] // h, by_state.shape[-1], by_state.shape[2]


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def chunk_states(x, w, b, per_step: int, interpret: bool):
    """x (n, T, h p) float32, w (n, c, h, q) float32, b (n, c, g, q, s)
    float32 -> (n, c, s, h p) float32: what each chunk adds to the state, ``B^T (w
    x)`` with ``w = dt exp(cum_last - cum)``."""
    return _states_fwd(x, w, b, per_step, interpret)[0]


def _states_fwd(x, w, b, per_step, interpret):
    b = precision.cast_compute(b)
    added, = _run("ssd_states_fwd", _dims(w, x, b), per_step, interpret,
                  x, w, jnp.swapaxes(b, 3, 4))
    return added, (x, w, b)


def _states_bwd(per_step, interpret, residuals, da):
    x, w, b = residuals
    return tuple(_run("ssd_states_bwd", _dims(w, x, b), per_step, interpret,
                      x, w, b, da))


chunk_states.defvjp(_states_fwd, _states_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def chunk_outputs(cb, dt, cum, x, c, entering, d, per_step: int, interpret: bool):
    """``C B^T`` (n, c, g, q, q), dt and the running sums ``cum`` (n, c, h, q),
    x (n, T, h p), c (n, c, g, q, s), the states ``entering`` the chunks (n, c,
    s, h p), d (1, h p), all float32 -> y (n, T, h p) float32."""
    return _outputs_fwd(cb, dt, cum, x, c, entering, d, per_step, interpret)[0]


def _outputs_fwd(cb, dt, cum, x, c, entering, d, per_step, interpret):
    c, entering = precision.cast_compute(c), precision.cast_compute(entering)
    y, = _run("ssd_outputs_fwd", _dims(cum, x, c), per_step, interpret,
              cb, dt, cum, x, c, entering, d)
    return y, (cb, dt, cum, x, c, entering, d)


def _outputs_bwd(per_step, interpret, residuals, dy):
    cb, dt, cum, x, c, entering, d = residuals
    dx, ddt, dcum, dcbt, dc, ds, dd = _run(
        "ssd_outputs_bwd", _dims(cum, x, c), per_step, interpret,
        jnp.swapaxes(cb, 3, 4), dt, cum, x, c, jnp.swapaxes(c, 3, 4), entering,
        d, dy)
    return (jnp.swapaxes(dcbt, 3, 4), ddt, dcum, dx, dc, ds,
            jnp.sum(dd, axis=(0, 1)))


chunk_outputs.defvjp(_outputs_fwd, _outputs_bwd)
