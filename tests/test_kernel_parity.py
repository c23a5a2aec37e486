"""CPU interpret-mode parity matrix over EVERY Pallas kernel in ops/
(tools/check.sh --kernels gate).

Each kernel runs as its jnp-level interpretation under JAX_PLATFORMS=cpu
(the same program Mosaic compiles on TPU, minus the scheduling) and is
checked — forward AND custom-VJP gradients — against the plain-jnp reference
it replaces, across dtypes (f32/bf16) and ragged shapes (dims that are not
lane/sublane multiples, plus row counts that do not divide the kernels'
block size). f32 parity is the ≤1e-5 acceptance lock; bf16 uses the wider
tolerance its 8-bit mantissa implies (the jnp references themselves compute
some statistics in bf16 where the kernels hold fp32 registers).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops import fused_epilogue, fused_norm
from bigdl_tpu.ops.flash_attention import _dense_reference, flash_attention
from bigdl_tpu.ops.maxpool import _maxpool_grad_nchw, maxpool_grad_reference

F32_TOL = 1e-5   # the acceptance-criteria lock
BF16_TOL = 5e-2

# tier-1 runs the f32 locks; the bf16 half (and the flash duplicates below —
# test_flash_attention.py already covers that kernel in tier-1) is slow-marked
# so the tier-1 window holds. `tools/check.sh --kernels` runs the FULL matrix.
DTYPES = (
    jnp.float32,
    pytest.param(jnp.bfloat16, marks=pytest.mark.slow),
)
# (rows..., hidden): aligned + ragged (non-128 lanes, non-8 sublanes, and a
# row count that does not divide the row-block size)
NORM_SHAPES = (((8,), 128), ((5, 3), 33), ((257,), 96))


def _tol(dtype):
    return F32_TOL if dtype == jnp.float32 else BF16_TOL


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape).astype(dtype)


def _close(a, b, tol, what):
    """Scaled closeness: |Δ| ≤ tol · (1 + max|ref|) — the f32 lock stays
    ≤1e-5 in units of the reference's own magnitude (reductions over
    hundreds of rows legitimately reassociate)."""
    bf = b.astype(jnp.float32)
    diff = float(jnp.max(jnp.abs(a.astype(jnp.float32) - bf)))
    scale = 1.0 + float(jnp.max(jnp.abs(bf)))
    assert diff <= tol * scale, (
        f"{what}: max |Δ| = {diff} > {tol} * {scale}"
    )


def _grads_close(f_kernel, f_ref, args, argnums, tol, what):
    loss_k = lambda *a: jnp.sum(jnp.sin(f_kernel(*a).astype(jnp.float32)))  # noqa: E731
    loss_r = lambda *a: jnp.sum(jnp.sin(f_ref(*a).astype(jnp.float32)))  # noqa: E731
    gk = jax.grad(loss_k, argnums=argnums)(*args)
    gr = jax.grad(loss_r, argnums=argnums)(*args)
    for i, (a, b) in enumerate(zip(gk, gr)):
        _close(a, b, tol, f"{what} grad[{argnums[i]}]")


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("lead,h", NORM_SHAPES, ids=("aligned", "ragged", "tallragged"))
class TestFusedNormParity:
    def test_layer_norm(self, lead, h, dtype):
        x = _rand(jax.random.PRNGKey(0), lead + (h,), dtype)
        w = _rand(jax.random.PRNGKey(1), (h,), jnp.float32)
        b = _rand(jax.random.PRNGKey(2), (h,), jnp.float32)
        fused = lambda x, w, b: fused_norm.fused_layer_norm(x, w, b, 1e-5)  # noqa: E731
        ref = lambda x, w, b: fused_norm.layer_norm_reference(x, w, b, 1e-5)  # noqa: E731
        _close(fused(x, w, b), ref(x, w, b), _tol(dtype), "layer_norm fwd")
        _grads_close(fused, ref, (x, w, b), (0, 1, 2), _tol(dtype),
                     "layer_norm")

    def test_rms_norm(self, lead, h, dtype):
        x = _rand(jax.random.PRNGKey(3), lead + (h,), dtype)
        w = _rand(jax.random.PRNGKey(4), (h,), jnp.float32)
        fused = lambda x, w: fused_norm.fused_rms_norm(x, w, 1e-6)  # noqa: E731
        ref = lambda x, w: fused_norm.rms_norm_reference(x, w, 1e-6)  # noqa: E731
        _close(fused(x, w), ref(x, w), _tol(dtype), "rms_norm fwd")
        _grads_close(fused, ref, (x, w), (0, 1), _tol(dtype), "rms_norm")


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("act", fused_epilogue.ACTIVATIONS,
                         ids=("none", "relu", "gelu", "tanh"))
class TestFusedEpilogueParity:
    def test_feature_bias(self, act, dtype):
        x = _rand(jax.random.PRNGKey(5), (9, 37), dtype)  # ragged both dims
        b = _rand(jax.random.PRNGKey(6), (37,), jnp.float32)
        ref_act = fused_epilogue.act_reference(act)
        fused = lambda x, b: fused_epilogue.fused_bias_act(x, b, act, -1)  # noqa: E731
        ref = lambda x, b: ref_act(x + b.astype(x.dtype))  # noqa: E731
        _close(fused(x, b), ref(x, b), _tol(dtype), f"bias_{act} fwd")
        _grads_close(fused, ref, (x, b), (0, 1), _tol(dtype), f"bias_{act}")

    def test_channel_bias_nchw(self, act, dtype):
        x = _rand(jax.random.PRNGKey(7), (3, 5, 6, 7), dtype)  # all ragged
        b = _rand(jax.random.PRNGKey(8), (5,), jnp.float32)
        ref_act = fused_epilogue.act_reference(act)
        fused = lambda x, b: fused_epilogue.fused_bias_act(x, b, act, 1)  # noqa: E731
        ref = lambda x, b: ref_act(  # noqa: E731
            x + b.astype(x.dtype)[None, :, None, None])
        _close(fused(x, b), ref(x, b), _tol(dtype), f"chan_bias_{act} fwd")
        _grads_close(fused, ref, (x, b), (0, 1), _tol(dtype),
                     f"chan_bias_{act}")


@pytest.mark.slow  # tier-1 covers this kernel via tests/test_flash_attention.py
@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("tile", (64, None), ids=("tile64", "rule"))
@pytest.mark.parametrize("tq,tk", ((128, 128), (96, 160)),
                         ids=("square", "rect"))
def test_flash_attention_parity(tq, tk, dtype, tile):
    """The pre-existing flash kernel rides the same gate: fwd + gradients vs the
    dense softmax reference, in interpret mode; at tiles of 64 and at the
    tiles the rule picks from the shapes."""
    n, h, d = 1, 2, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(9), 3)
    q = _rand(kq, (n, h, tq, d), dtype)
    k = _rand(kk, (n, h, tk, d), dtype)
    v = _rand(kv, (n, h, tk, d), dtype)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2  # softmax chain: looser f32
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=tile, block_k=tile)
    ref = _dense_reference(q, k, v, True, None)
    _close(out, ref, tol, "flash fwd")
    # dQ, dK and dV: one backward kernel feeds all three
    gk = jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, causal=True, interpret=True, block_q=tile,
                        block_k=tile).astype(jnp.float32) ** 2),
        (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(
        _dense_reference(*a, True, None).astype(jnp.float32) ** 2),
        (0, 1, 2))(q, k, v)
    for got, want, name in zip(gk, gr, ("dq", "dk", "dv")):
        _close(got, want, tol, f"flash {name}")


@pytest.mark.parametrize("dtype", (jnp.float32,), ids=("f32",))
@pytest.mark.parametrize(
    "hw,kernel,stride,pad",
    (
        ((12, 12), (2, 2), (2, 2), ((0, 0), (0, 0))),
        ((11, 13), (3, 3), (2, 2), ((1, 1), (1, 1))),  # ragged + padded
    ),
    ids=("even", "ragged"),
)
def test_maxpool_grad_parity(hw, kernel, stride, pad, dtype):
    """The pre-existing maxpool backward kernel in the same matrix: the
    Pallas dx vs XLA's SelectAndScatter gradient (bf16 is skipped — the
    kernel is gated f32-only on the training path)."""
    h, w = hw
    x = _rand(jax.random.PRNGKey(10), (2, 3, h, w), dtype)
    import jax.numpy as jnp  # local: lax closure below

    from jax import lax

    kh, kw = kernel
    sh, sw = stride
    (ph, _), (pw, _) = pad
    ho = (h + 2 * ph - kh) // sh + 1
    wo = (w + 2 * pw - kw) // sw + 1
    dy = _rand(jax.random.PRNGKey(11), (2, 3, ho, wo), dtype)
    dx = _maxpool_grad_nchw(x, dy, kernel, stride, (ph, pw), (ho, wo),
                            interpret=True)
    ref = maxpool_grad_reference(x, dy, kernel, stride, pad)
    _close(dx, ref, F32_TOL, "maxpool dx")
