"""Pallas maxpool-backward kernel parity vs XLA's SelectAndScatter.

The kernel recomputes the windowed argmax from x, so the oracle is simply
the vjp XLA itself derives for ``lax.reduce_window(max)`` — including its
first-element-in-scan-order tie-breaking, which the constant-input and
duplicate-value cases below pin down explicitly.

Runs in Pallas interpret mode (CPU); the TPU lowering is exercised by
``chip_smoke.py`` on the real chip.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from bigdl_tpu.ops.maxpool import (
    _maxpool_grad_nchw,
    maxpool_grad_reference,
    maxpool_grad_shift,
)


def _run(x, dy, kernel, stride, padding):
    ref = maxpool_grad_reference(jnp.asarray(x), jnp.asarray(dy),
                                 kernel, stride, padding)
    (ph, _), (pw, _) = padding
    got = _maxpool_grad_nchw(jnp.asarray(x), jnp.asarray(dy), kernel, stride,
                             (ph, pw), dy.shape[2:], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)


def _case(n, c, h, w, kernel, stride, padding, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, c, h, w)).astype(np.float32)
    kh, kw = kernel
    sh, sw = stride
    (pl_, ph_), (pw_, pr_) = padding
    ho = (h + pl_ + ph_ - kh) // sh + 1
    wo = (w + pw_ + pr_ - kw) // sw + 1
    dy = rng.standard_normal((n, c, ho, wo)).astype(np.float32)
    return x, dy


class TestMaxpoolGradParity:
    @pytest.mark.parametrize("kernel,stride,padding", [
        ((2, 2), (2, 2), ((0, 0), (0, 0))),   # non-overlapping
        ((3, 3), (2, 2), ((0, 0), (0, 0))),   # inception 3x3/s2
        ((3, 3), (2, 2), ((1, 1), (1, 1))),   # resnet stem 3x3/s2/p1
        ((3, 3), (1, 1), ((1, 1), (1, 1))),   # inception 3x3/s1 SAME-ish
        ((3, 2), (2, 1), ((1, 0), (0, 1))),   # asymmetric everything
        ((2, 2), (2, 2), ((0, 1), (0, 1))),   # ceil-mode overhang padding
    ])
    def test_geometries(self, kernel, stride, padding):
        x, dy = _case(2, 3, 13, 11, kernel, stride, padding, seed=0)
        _run(x, dy, kernel, stride, padding)

    def test_overlapping_window_ties(self):
        # constant input: every window element ties; gradient must go to the
        # FIRST element in row-major scan order of each window, exactly as
        # SelectAndScatter routes it
        x = np.zeros((1, 2, 8, 8), np.float32)
        dy = np.arange(1 * 2 * 4 * 4, dtype=np.float32).reshape(1, 2, 4, 4) + 1
        _run(x, dy, (3, 3), (2, 2), ((1, 1), (1, 1)))

    def test_duplicate_maxima_within_window(self):
        # crafted duplicates at different in-window offsets
        rng = np.random.default_rng(3)
        x = rng.integers(0, 3, (2, 2, 10, 10)).astype(np.float32)
        dy = rng.standard_normal((2, 2, 5, 5)).astype(np.float32)
        _run(x, dy, (2, 2), (2, 2), ((0, 0), (0, 0)))
        dy2 = rng.standard_normal((2, 2, 4, 4)).astype(np.float32)
        _run(x, dy2, (3, 3), (2, 2), ((0, 0), (0, 0)))

    def test_stride_larger_than_kernel_skips_rows(self):
        # floor mode can leave trailing input rows untouched (zero grad)
        x, dy = _case(1, 1, 9, 9, (2, 2), (3, 3), ((0, 0), (0, 0)), seed=5)
        _run(x, dy, (2, 2), (3, 3), ((0, 0), (0, 0)))

    def test_bf16(self):
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.standard_normal((2, 4, 12, 12)), jnp.bfloat16)
        dy = jnp.asarray(rng.standard_normal((2, 4, 6, 6)), jnp.bfloat16)
        ref = maxpool_grad_reference(x, dy, (3, 3), (2, 2),
                                     ((1, 1), (1, 1)))
        got = _maxpool_grad_nchw(x, dy, (3, 3), (2, 2), (1, 1), (6, 6),
                                 interpret=True)
        # overlapping windows sum 2+ contributions per position in a
        # different order than SelectAndScatter -> bf16 rounding skew
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=1e-2, atol=2e-2)

    def test_large_channel_count_grid_split(self):
        # NC bigger than one block: exercises the channel-slab grid
        x, dy = _case(4, 64, 14, 14, (3, 3), (2, 2), ((1, 1), (1, 1)), seed=9)
        _run(x, dy, (3, 3), (2, 2), ((1, 1), (1, 1)))


class TestShiftImplParity:
    """Pure-XLA shift decomposition (maxpool_grad_shift) vs the oracle.

    On continuous inputs (measure-zero ties) it must match SelectAndScatter
    exactly; on ties it deliberately differs (gradient to every tied max),
    pinned below."""

    @pytest.mark.parametrize("kernel,stride,padding", [
        ((2, 2), (2, 2), ((0, 0), (0, 0))),
        ((3, 3), (2, 2), ((0, 0), (0, 0))),
        ((3, 3), (2, 2), ((1, 1), (1, 1))),
        ((3, 3), (1, 1), ((1, 1), (1, 1))),
        ((3, 2), (2, 1), ((1, 0), (0, 1))),
        ((2, 2), (2, 2), ((0, 1), (0, 1))),
        ((2, 2), (3, 3), ((0, 0), (0, 0))),   # stride > kernel
    ])
    def test_geometries_match_oracle(self, kernel, stride, padding):
        x, dy = _case(2, 3, 13, 11, kernel, stride, padding, seed=21)
        ref = maxpool_grad_reference(jnp.asarray(x), jnp.asarray(dy),
                                     kernel, stride, padding)
        got = maxpool_grad_shift(jnp.asarray(x), jnp.asarray(dy),
                                 kernel, stride, padding)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-6)

    def test_tie_semantics_distribute(self):
        # constant input, non-overlapping 2x2: SelectAndScatter routes dy to
        # the first element; shift routes it to ALL four tied positions.
        # Gradient mass per window is 4x dy — the documented difference.
        x = jnp.zeros((1, 1, 4, 4), jnp.float32)
        dy = jnp.ones((1, 1, 2, 2), jnp.float32)
        got = np.asarray(maxpool_grad_shift(x, dy, (2, 2), (2, 2),
                                            ((0, 0), (0, 0))))
        np.testing.assert_allclose(got, np.ones((1, 1, 4, 4)))

    def test_env_selects_shift_in_module_backward(self, monkeypatch):
        """Discriminating input: constant plateau, where shift's
        distribute-to-all-ties gradient DIFFERS from SelectAndScatter —
        so a broken env selection cannot pass by accident (r5 review)."""
        import jax

        from bigdl_tpu.ops import maxpool as M

        monkeypatch.setenv("BIGDL_MAXPOOL_GRAD_IMPL", "shift")
        x = jnp.zeros((1, 1, 4, 4), jnp.float32)
        kernel, stride, pad = (2, 2), (2, 2), ((0, 0), (0, 0))

        def f(v):
            return jnp.sum(M.maxpool2d(v, kernel, stride, pad))

        g = np.asarray(jax.grad(f)(x))
        # shift: every tied position gets dy=1; SAS would leave a sparse
        # one-per-window pattern
        np.testing.assert_allclose(g, np.ones((1, 1, 4, 4)))

    def test_unknown_impl_env_warns_and_defaults(self, monkeypatch):
        from bigdl_tpu.ops import maxpool as M

        monkeypatch.setenv("BIGDL_MAXPOOL_GRAD_IMPL", "shif")
        with pytest.warns(RuntimeWarning, match="not recognized"):
            assert M._grad_impl() == "sas"
        monkeypatch.setenv("BIGDL_MAXPOOL_GRAD_IMPL", "xla")
        assert M._grad_impl() == "sas"


class TestModuleIntegration:
    def test_spatial_max_pooling_backward_matches_xla(self):
        import bigdl_tpu.nn as nn

        rng = np.random.default_rng(11)
        x = rng.standard_normal((2, 3, 9, 9)).astype(np.float32)
        m = nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1)
        y = m.forward(x)
        dy = rng.standard_normal(np.asarray(y).shape).astype(np.float32)
        dx = np.asarray(m.backward(x, dy))
        ref = maxpool_grad_reference(jnp.asarray(x), jnp.asarray(dy),
                                     (3, 3), (2, 2), ((1, 1), (1, 1)))
        np.testing.assert_allclose(dx, np.asarray(ref), atol=1e-6)

    def test_ceil_mode_backward(self):
        import bigdl_tpu.nn as nn

        rng = np.random.default_rng(12)
        x = rng.standard_normal((1, 2, 10, 10)).astype(np.float32)
        m = nn.SpatialMaxPooling(3, 3, 2, 2).ceil()
        y = m.forward(x)
        assert np.asarray(y).shape[-1] == 5  # ceil sizing (floor gives 4)
        dy = rng.standard_normal(np.asarray(y).shape).astype(np.float32)
        dx = np.asarray(m.backward(x, dy))
        assert dx.shape == x.shape
        # total gradient mass is conserved (each window routes its dy once)
        np.testing.assert_allclose(dx.sum(), dy.sum(), rtol=1e-5)
