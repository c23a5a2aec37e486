"""Pallas flash-attention kernel vs dense oracle (interpret mode on CPU).

The dnn-vs-blas parity trick from the reference's test strategy (SURVEY.md §4):
the hand-scheduled kernel is checked against the straightforward jnp path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.nn.attention import (
    attention_bias_lower_triangle,
    scaled_dot_product_attention,
)
from bigdl_tpu.ops import flash_attention


def _qkv(n=2, h=3, tq=32, tk=32, d=16, seed=0):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.standard_normal((n, h, tq, d)), jnp.float32)
    k = jnp.asarray(r.standard_normal((n, h, tk, d)), jnp.float32)
    v = jnp.asarray(r.standard_normal((n, h, tk, d)), jnp.float32)
    return q, k, v


class TestFlashForward:
    def test_matches_dense(self):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True)
        ref = scaled_dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_causal_matches_dense(self):
        q, k, v = _qkv(seed=1)
        out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                              interpret=True)
        ref = scaled_dot_product_attention(
            q, k, v, attention_bias_lower_triangle(q.shape[2])
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_causal_rectangular_decode_shape(self):
        """Tq != Tk causal: aligned at the end (1-query decode sees all keys)."""
        q, k, v = _qkv(tq=1, tk=24, seed=8)
        out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                              interpret=True)
        ref = scaled_dot_product_attention(q, k, v)  # full visibility
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        # and a mid-sequence rectangle agrees with the dense causal path
        q2, k2, v2 = _qkv(tq=8, tk=24, seed=9)
        out2 = flash_attention(q2, k2, v2, causal=True, block_q=8, block_k=8,
                               interpret=True)
        ref2 = scaled_dot_product_attention(q2, k2, v2, causal=True)
        np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2), atol=1e-5)

    def test_ragged_length_padding(self):
        """T not a multiple of the block size: padded keys must not leak."""
        q, k, v = _qkv(tq=13, tk=21, seed=2)
        out = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True)
        ref = scaled_dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_cross_attention_lengths(self):
        q, k, v = _qkv(tq=16, tk=48, seed=3)
        out = flash_attention(q, k, v, block_q=8, block_k=16, interpret=True)
        ref = scaled_dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_under_jit(self):
        q, k, v = _qkv(seed=4)
        f = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, True, None, 8, 8, True)
        )
        ref = scaled_dot_product_attention(
            q, k, v, attention_bias_lower_triangle(q.shape[2])
        )
        np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(ref),
                                   atol=1e-5)


class TestFlashBackward:
    def test_grads_match_dense(self):
        q, k, v = _qkv(tq=16, tk=16, seed=5)

        def flash_loss(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, True, None, 8, 8, True) ** 2
            )

        def dense_loss(q, k, v):
            bias = attention_bias_lower_triangle(q.shape[2])
            return jnp.sum(scaled_dot_product_attention(q, k, v, bias) ** 2)

        gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


class TestSdpaRouting:
    def test_impl_flash_falls_back_with_bias(self):
        q, k, v = _qkv(seed=6)
        bias = attention_bias_lower_triangle(q.shape[2])
        # bias present -> dense path even when flash requested
        out = scaled_dot_product_attention(q, k, v, bias, impl="flash")
        ref = scaled_dot_product_attention(q, k, v, bias)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    def test_causal_flag_dense_path(self):
        q, k, v = _qkv(seed=7)
        out = scaled_dot_product_attention(q, k, v, causal=True)
        ref = scaled_dot_product_attention(
            q, k, v, attention_bias_lower_triangle(q.shape[2])
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_causal_rect_fully_masked_rows_grad_finite():
    """Round-1 advisor finding: Tq > Tk causal rows with no visible keys gave
    nan gradients from the dense-recompute backward while the flash forward
    returned 0 — they must agree (zero output, finite grads)."""
    import jax

    from bigdl_tpu.ops.flash_attention import _dense_reference

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 1, 4, 8)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((1, 1, 2, 8)), jnp.float32)

    out = _dense_reference(q, kv, kv, causal=True, scale=None)
    # rows 0..1 have no visible keys under the aligned-at-end convention
    np.testing.assert_allclose(np.asarray(out[0, 0, :2]), 0.0, atol=1e-6)

    g = jax.grad(lambda q: jnp.sum(_dense_reference(q, kv, kv, True, None) ** 2))(q)
    assert np.all(np.isfinite(np.asarray(g)))


class TestFlashPallasBackward:
    """Round-2: the backward is now a pair of Pallas kernels (dQ, dK/dV)
    streaming off the saved logsumexp — checked against the dense vjp."""

    def _check(self, tq, tk, causal, seed, bq=8, bk=8):
        q, k, v = _qkv(tq=tq, tk=tk, seed=seed)

        def flash_loss(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal, None, bq, bk, True) ** 2
            )

        def dense_loss(q, k, v):
            from bigdl_tpu.ops.flash_attention import _dense_reference
            return jnp.sum(_dense_reference(q, k, v, causal, None) ** 2)

        gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=1e-3)

    def test_square_noncausal(self):
        self._check(16, 16, False, 10)

    def test_square_causal(self):
        self._check(16, 16, True, 11)

    def test_ragged_lengths(self):
        """T not a multiple of the block: padded rows/cols contribute zero."""
        self._check(13, 21, False, 12)

    def test_rect_causal_decode(self):
        self._check(8, 24, True, 13)

    def test_rect_causal_fully_masked_rows(self):
        """Tq > Tk causal: head rows see no keys; grads must be finite zero
        through the PALLAS backward, not just the dense reference."""
        q, k, v = _qkv(tq=4, tk=2, d=8, seed=14)
        g = jax.grad(
            lambda q: jnp.sum(flash_attention(q, k, v, True, None, 8, 8, True) ** 2)
        )(q)
        arr = np.asarray(g)
        assert np.all(np.isfinite(arr))
        np.testing.assert_allclose(arr[:, :, :2], 0.0, atol=1e-6)

    def test_under_jit_grad(self):
        q, k, v = _qkv(tq=16, tk=16, seed=15)
        f = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, True, None, 8, 8, True)
            ),
            argnums=(0, 1, 2),
        ))
        for leaf in f(q, k, v):
            assert np.all(np.isfinite(np.asarray(leaf)))


class TestFlashLengthsMasking:
    """Per-batch padding masks (VERDICT r3 weak #2): padded variable-length
    batches must stay on the kernel path with exact masked semantics."""

    @staticmethod
    def _dense_masked(q, k, v, lengths, causal=False):
        import math as _math

        n, h, t, d = q.shape
        s = jnp.einsum("nhqd,nhkd->nhqk", q, k).astype(jnp.float32)
        s = s / _math.sqrt(d)
        rows = jnp.arange(t)[:, None]
        cols = jnp.arange(t)[None, :]
        allowed = (cols[None] < lengths[:, None, None]) \
            & (rows[None] < lengths[:, None, None])
        if causal:
            allowed = allowed & (rows >= cols)[None]
        allowed = allowed[:, None]  # broadcast over heads
        s = jnp.where(allowed, s, -jnp.inf)
        row_has = allowed.any(-1, keepdims=True)
        s = jnp.where(row_has, s, 0.0)
        w = jnp.where(row_has, jax.nn.softmax(s, axis=-1), 0.0)
        return jnp.einsum("nhqk,nhkd->nhqd", w.astype(q.dtype), v)

    def test_forward_matches_dense_masked(self):
        q, k, v = _qkv(n=3, h=2, tq=32, tk=32, seed=21)
        lengths = jnp.asarray([32, 17, 9], jnp.int32)
        out = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True,
                              lengths=lengths)
        ref = self._dense_masked(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        # padded query rows are exactly zero
        assert float(jnp.abs(out[1, :, 17:]).max()) == 0.0
        assert float(jnp.abs(out[2, :, 9:]).max()) == 0.0

    def test_forward_causal_plus_lengths(self):
        q, k, v = _qkv(n=2, h=2, tq=32, tk=32, seed=22)
        lengths = jnp.asarray([29, 11], jnp.int32)
        out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                              interpret=True, lengths=lengths)
        ref = self._dense_masked(q, k, v, lengths, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_grads_match_dense_masked(self):
        q, k, v = _qkv(n=2, h=2, tq=24, tk=24, seed=23)
        lengths = jnp.asarray([24, 13], jnp.int32)
        # upstream grad deliberately NONZERO at padded positions: the kernel
        # must not leak it into dk/dv
        g = jnp.asarray(
            np.random.default_rng(5).standard_normal(q.shape), jnp.float32)

        def flash_loss(q, k, v):
            out = flash_attention(q, k, v, block_q=8, block_k=8,
                                  interpret=True, lengths=lengths)
            return jnp.sum(out * g)

        def dense_loss(q, k, v):
            # dense loss only counts valid rows (the kernel zeroes padded
            # rows, so its padded-row output contributes nothing)
            out = self._dense_masked(q, k, v, lengths)
            rows = jnp.arange(q.shape[2])[None, None, :, None]
            valid = rows < lengths[:, None, None, None]
            return jnp.sum(jnp.where(valid, out * g, 0.0))

        gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, err_msg=name)
            # gradients at padded positions are exactly zero
            np.testing.assert_array_equal(np.asarray(a)[1, :, 13:], 0.0)

    def test_grads_causal_plus_lengths(self):
        q, k, v = _qkv(n=2, h=2, tq=24, tk=24, seed=24)
        lengths = jnp.asarray([19, 24], jnp.int32)

        def flash_loss(q, k, v):
            out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                                  interpret=True, lengths=lengths)
            return jnp.sum(out ** 2)

        def dense_loss(q, k, v):
            return jnp.sum(self._dense_masked(q, k, v, lengths,
                                              causal=True) ** 2)

        gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, err_msg=name)

    def test_cross_attention_key_lengths(self):
        # Tq != Tk: lengths masks the (padded) memory KEYS only — the
        # encoder-memory case in the translation Transformer
        q, k, v = _qkv(n=2, h=2, tq=8, tk=32, seed=25)
        lengths = jnp.asarray([32, 14], jnp.int32)
        out = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True,
                              lengths=lengths)
        s = jnp.einsum("nhqd,nhkd->nhqk", q, k) / np.sqrt(q.shape[-1])
        mask = (jnp.arange(32)[None, :] < lengths[:, None])[:, None, None]
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        ref = jnp.einsum("nhqk,nhkd->nhqd", w, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_cross_attention_key_lengths_grads(self):
        q, k, v = _qkv(n=2, h=2, tq=8, tk=24, seed=27)
        lengths = jnp.asarray([24, 10], jnp.int32)

        def flash_loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, block_q=8, block_k=8, interpret=True,
                lengths=lengths) ** 2)

        def dense_loss(q, k, v):
            s = jnp.einsum("nhqd,nhkd->nhqk", q, k) / np.sqrt(q.shape[-1])
            mask = (jnp.arange(24)[None, :] < lengths[:, None])[:, None, None]
            w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            return jnp.sum(jnp.einsum("nhqk,nhkd->nhqd", w, v) ** 2)

        gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, err_msg=name)
            # masked key rows get exactly zero dk/dv
        np.testing.assert_array_equal(np.asarray(gf[1])[1, :, 10:], 0.0)
        np.testing.assert_array_equal(np.asarray(gf[2])[1, :, 10:], 0.0)

    def test_under_jit_with_lengths(self):
        q, k, v = _qkv(n=2, h=2, tq=32, tk=32, seed=26)
        lengths = jnp.asarray([32, 20], jnp.int32)
        f = jax.jit(lambda q, k, v, L: flash_attention(
            q, k, v, block_q=8, block_k=8, interpret=True, lengths=L))
        out = f(q, k, v, lengths)
        ref = self._dense_masked(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)


class TestCrossAttentionMaskQ:
    """Round-4 advisor HIGH finding: cross-attention where src and tgt are
    padded to the SAME T must not zero valid decoder query rows — query
    masking is explicit (``mask_q``), never inferred from Tq == Tk."""

    @staticmethod
    def _dense_key_masked(q, k, v, lengths):
        s = jnp.einsum("nhqd,nhkd->nhqk", q, k) / np.sqrt(q.shape[-1])
        tk = k.shape[2]
        mask = (jnp.arange(tk)[None, :] < lengths[:, None])[:, None, None]
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("nhqk,nhkd->nhqd", w, v)

    def test_equal_length_cross_valid_query_rows_survive(self):
        # target longer than its source: query rows >= src_len are VALID
        q, k, v = _qkv(n=2, h=2, tq=32, tk=32, seed=30)
        src_lengths = jnp.asarray([32, 12], jnp.int32)
        out = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True,
                              lengths=src_lengths, mask_q=False)
        ref = self._dense_key_masked(q, k, v, src_lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        # the decoder rows the old Tq==Tk heuristic zeroed are intact
        assert float(jnp.abs(out[1, :, 12:]).min()) > 0.0

    def test_equal_length_cross_grads(self):
        q, k, v = _qkv(n=2, h=2, tq=24, tk=24, seed=31)
        src_lengths = jnp.asarray([24, 9], jnp.int32)

        def flash_loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, block_q=8, block_k=8, interpret=True,
                lengths=src_lengths, mask_q=False) ** 2)

        def dense_loss(q, k, v):
            return jnp.sum(self._dense_key_masked(q, k, v, src_lengths) ** 2)

        gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, err_msg=name)
        # dq at rows >= src_len is nonzero (they are real queries) ...
        assert float(jnp.abs(np.asarray(gf[0])[1, :, 9:]).max()) > 0.0
        # ... while masked keys still get exactly zero dk/dv
        np.testing.assert_array_equal(np.asarray(gf[1])[1, :, 9:], 0.0)
        np.testing.assert_array_equal(np.asarray(gf[2])[1, :, 9:], 0.0)

    def test_sdpa_dense_fallback_mask_q_false(self):
        # same adversarial shape through scaled_dot_product_attention's
        # dense fallback (the advisor flagged the same heuristic there)
        q, k, v = _qkv(n=2, h=2, tq=16, tk=16, seed=32)
        src_lengths = jnp.asarray([16, 6], jnp.int32)
        out = scaled_dot_product_attention(q, k, v, impl="dense",
                                           lengths=src_lengths, mask_q=False)
        ref = self._dense_key_masked(q, k, v, src_lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        assert float(jnp.abs(out[1, :, 6:]).min()) > 0.0

    def test_mask_q_true_rectangular_aligned_at_end(self):
        # explicit mask_q=True with Tq != Tk follows the aligned-at-end row
        # convention (row i ↔ global position i + Tk - Tq), matching causal
        q, k, v = _qkv(n=1, h=1, tq=8, tk=16, seed=33)
        lengths = jnp.asarray([12], jnp.int32)
        out = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True,
                              lengths=lengths, mask_q=True)
        # rows with global position >= 12 (i.e. i + 8 >= 12 → i >= 4) zeroed
        np.testing.assert_array_equal(np.asarray(out)[0, :, 4:], 0.0)
        assert float(jnp.abs(out[0, :, :4]).min()) > 0.0


# --------------------------------------------------------------------------
# the backward's two forms (PR 38): the one kernel that builds each tile's p
# and dp once, with dK/dV accumulated over the whole key axis in VMEM, and
# the pair that a key axis too long for that accumulator keeps
# --------------------------------------------------------------------------
def _dense_masked_reference(q, k, v, causal, window, lengths, mask_q):
    """``_dense_reference`` where there are no lengths; with them, dense
    attention whose keys (and, with ``mask_q``, query rows) past a
    sequence's length are masked, rows without a key giving zero."""
    from bigdl_tpu.ops.flash_attention import _dense_reference

    if lengths is None:
        return _dense_reference(q, k, v, causal, None, window)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    tq, tk = q.shape[2], k.shape[2]
    rows = (jnp.arange(tq) + (tk - tq))[None, :, None]
    cols = jnp.arange(tk)[None, None, :]
    horizon = lengths[:, None, None]
    allowed = cols < horizon
    if mask_q:
        allowed = allowed & (rows < horizon)
    if causal:
        allowed = allowed & (rows >= cols)
    if window is not None:
        allowed = allowed & (rows - cols < window)
    allowed = allowed[:, None]
    s = jnp.einsum("nhqd,nhkd->nhqk", q, k) / np.sqrt(q.shape[-1])
    has = allowed.any(-1, keepdims=True)
    w = jnp.where(has, jax.nn.softmax(
        jnp.where(has, jnp.where(allowed, s, -jnp.inf), 0.0), axis=-1), 0.0)
    return jnp.einsum("nhqk,nhkd->nhqd", w, v)


# n, query heads, K/V heads, Tq, Tk, d, d_v, causal, window, lengths,
# mask_q, tile: every case has more than one tile on each axis
BACKWARD_CASES = {
    "full": (2, 2, 2, 32, 32, 16, 16, False, None, None, True, 8),
    "causal": (2, 2, 2, 32, 32, 16, 16, True, None, None, True, 8),
    "window": (1, 2, 2, 640, 640, 16, 16, True, 300, None, True, 128),
    "group2": (1, 4, 2, 48, 48, 16, 16, True, None, None, True, 16),
    "group4+window": (1, 4, 1, 640, 640, 16, 16, True, 300, None, True, 128),
    "lengths+mask_q": (2, 4, 2, 40, 40, 16, 16, True, None, (17, 40), True, 8),
    "lengths-mask_q": (2, 2, 2, 40, 40, 16, 16, False, None, (17, 33), False, 8),
    "d_v=2d/3": (1, 2, 1, 320, 320, 24, 16, True, None, None, True, 128),
    "Tq<Tk": (2, 2, 2, 16, 40, 16, 16, True, None, None, False, 8),
    "Tq>Tk": (1, 2, 2, 40, 24, 16, 16, True, None, None, False, 8),
    "ragged-T": (2, 2, 2, 13, 21, 16, 16, False, None, None, False, 8),
    "rule-tiles": (1, 2, 1, 2304, 2304, 16, 16, True, 1100, None, True, None),
}


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "pair"])
@pytest.mark.parametrize("case", BACKWARD_CASES)
def test_backward_forms_match_dense(case, fused):
    """dQ, dK and dV of each form of the backward against dense attention's,
    and the forward with them."""
    from bigdl_tpu.ops.flash_attention import _flash_core, _resolve_tiles

    n, h, hkv, tq, tk, d, d_v, causal, window, lengths, mask_q, tile = \
        BACKWARD_CASES[case]
    rng = np.random.default_rng(len(case))
    q, k, v, w = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                  for shape in ((n, h, tq, d), (n, hkv, tk, d),
                                (n, hkv, tk, d_v), (n, h, tq, d_v)))
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
    bq, bk, _ = _resolve_tiles(q, k, v, causal, window, tile, tile)

    def flash(q, k, v):
        return _flash_core(q, k, v, lengths, causal, None, bq, bk, True,
                           mask_q, window, fused)

    def dense(q, k, v):
        return _dense_masked_reference(q, k, v, causal, window, lengths,
                                       mask_q)

    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(w * flash(*a)), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(w * dense(*a)), (0, 1, 2))(q, k, v)
    for g, r, like in zip(got, want, (q, k, v)):
        assert g.shape == like.shape
        np.testing.assert_allclose(g, r, atol=5e-5)


def test_backward_forms_sum_in_the_same_order():
    """A k tile meets its q tiles in one order in both forms (head by head
    of a group, q tile by q tile), so the float32 sums agree bit for bit."""
    from bigdl_tpu.ops.flash_attention import _flash_core

    rng = np.random.default_rng(5)
    q, w = (jnp.asarray(rng.standard_normal((2, 4, 48, 16)), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((2, 2, 48, 16)), jnp.float32)
            for _ in range(2))
    lengths = jnp.asarray((29, 48), jnp.int32)
    grads = [jax.grad(lambda *a: jnp.sum(w * _flash_core(
        *a, lengths, True, None, 16, 16, True, True, 20, fused)),
        (0, 1, 2))(q, k, v) for fused in (True, False)]
    for one, pair in zip(*grads):
        np.testing.assert_array_equal(one, pair)


def _kernel_names(fn, *args):
    import re

    return sorted(set(re.findall(r"name=(flash_(?:fwd|bwd)\w*)",
                                 str(jax.make_jaxpr(fn)(*args)))))


def test_the_backward_follows_the_shapes():
    """No argument chooses the backward: a key axis whose float32 dK/dV
    accumulator fits VMEM gets the one kernel, a longer one the pair, and
    the tile record says which and how large the accumulator is."""
    from bigdl_tpu.ops.flash_attention import (
        _dense_reference, backward_form, take_tile_records)

    grad = lambda q, k, v: jax.grad(lambda *a: jnp.sum(  # noqa: E731
        flash_attention(*a, interpret=True)), (0, 1, 2))(q, k, v)
    take_tile_records()
    q = jnp.ones((1, 2, 256, 16))
    assert _kernel_names(grad, q, q, q) == ["flash_bwd", "flash_fwd"]
    record, = take_tile_records()
    assert (record["backward"], record["backward_acc_bytes"]) == (
        "fused", 256 * (16 + 16) * 4)

    # 16 queries against 16384 float32 keys of 128: 16 MiB of accumulator,
    # and as much again twice over for the result blocks
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 1, 16, 128)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 1, 16384, 128)), jnp.float32)
            for _ in range(2))
    assert backward_form(16384, 16, 1024, 128, 4) == (False, 16 * 2 ** 20)
    assert backward_form(16384, 16, 1024, 128, 2) == (True, 16 * 2 ** 20)
    assert _kernel_names(grad, q, k, v) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    record, = take_tile_records()
    assert (record["backward"], record["backward_acc_bytes"]) == (
        "pair", 16 * 2 ** 20)
    want = jax.grad(lambda *a: jnp.sum(_dense_reference(*a, False, None)),
                    (0, 1, 2))(q, k, v)
    for g, r in zip(grad(q, k, v), want):
        np.testing.assert_allclose(g, r, atol=2e-5)


def _table_shapes():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "flash_tile_table.py")
    spec = importlib.util.spec_from_file_location("flash_tile_table", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.SHAPES


TABLE_SHAPES = _table_shapes()


@pytest.mark.parametrize("row", sorted(TABLE_SHAPES))
def test_the_tables_shapes_fit_the_limits(row):
    """Every shape of ``tools/flash_tile_table.py``: the rule's tiles keep
    the forward kernel under Mosaic's default limit, and the one backward
    kernel, which every one of them gets, under the limit it asks for."""
    from bigdl_tpu.ops.flash_attention import (
        _FUSED_VMEM_BUDGET, _VMEM_BUDGET, _VMEM_LIMIT, _working_set,
        backward_form, pick_tiles)

    shape = TABLE_SHAPES[row]
    t, d, d_v = shape["t"], shape["d"], shape.get("d_v")
    bq, bk = pick_tiles(t, t, d, 2, d_v)
    assert _working_set(bq, bk, d, 2, d_v) <= _VMEM_BUDGET
    fused, acc = backward_form(t, bq, bk, d, 2, d_v)
    assert fused and acc == t * (d + (d_v or d)) * 4
    assert _working_set(bq, bk, d, 2, d_v, tk=t) <= _FUSED_VMEM_BUDGET \
        < _VMEM_LIMIT
