"""Flash-with-lengths vs dense-with-bias on ragged batches — device-time A/B.

The round-3 weakness: padded variable-length batches silently fell back to
dense attention. This measures the kernel path's tok/s with ~30% padding
at T in {2048, 4096}, fwd+bwd, against the dense additive-bias path on
the same data. In-jit repetition divides out dispatch latency; scalar-pull
sync. Writes bench_artifacts/FLASH_LENGTHS_AB_r4.json.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402


def main() -> None:
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn.attention import (padding_attention_bias,
                                        scaled_dot_product_attention)

    from _bench_io import write_unless_clobbering

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"flash_lengths_ab measures the Mosaic kernel: needs the tpu "
            f"backend, found {jax.default_backend()!r}")
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "bench_artifacts", "FLASH_LENGTHS_AB_r4.json")

    R = 4
    rng = np.random.default_rng(0)
    wx = jnp.ones((1024, 1024), jnp.bfloat16)
    warm = jax.jit(lambda t: (t @ t).sum())
    for _ in range(3):
        _ = float(warm(wx))

    out = {"R_in_jit": R, "device": str(jax.devices()[0]),
           "shape": "h=8 d=64, ~30% padding; n=8@2k, n=4@4k", "cases": []}
    for t_len in (2048, 4096):
        # dense-side HBM: the grad residuals keep R softmax weight tensors
        # (n*h*T^2 f32) live — n=8 @ T=4096 is ~17 GB and OOMs the 16 GB
        # chip (observed r5 queue), so halve the batch at 4k
        n, h, d = (8 if t_len <= 2048 else 4), 8, 64
        q = jnp.asarray(rng.standard_normal((n, h, t_len, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((n, h, t_len, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((n, h, t_len, d)), jnp.bfloat16)
        lens = jnp.asarray(
            rng.integers(int(0.6 * t_len), int(0.8 * t_len), n), jnp.int32)
        pad = (jnp.arange(t_len)[None, :] >= lens[:, None]).astype(jnp.float32)
        bias = padding_attention_bias(pad)
        g = jnp.asarray(rng.standard_normal((n, h, t_len, d)), jnp.bfloat16)

        def loss(q, kk, vv, impl):
            acc = 0.0
            for i in range(R):
                o = scaled_dot_product_attention(
                    q + jnp.bfloat16(i) * jnp.bfloat16(1e-4), kk, vv,
                    bias=None if impl == "flash" else bias,
                    impl=impl, lengths=lens if impl == "flash" else None)
                acc = acc + jnp.sum(o.astype(jnp.float32)
                                    * g.astype(jnp.float32))
            return acc

        f_flash = jax.jit(jax.grad(lambda q, kk, vv: loss(q, kk, vv, "flash"),
                                   argnums=(0, 1, 2)))
        f_dense = jax.jit(jax.grad(lambda q, kk, vv: loss(q, kk, vv, "dense"),
                                   argnums=(0, 1, 2)))

        def timeit(fn, reps=6):
            fn(q, k, v)
            o = fn(q, k, v)
            _ = float(jnp.asarray(o[0]).ravel()[0].astype(jnp.float32))
            t0 = time.perf_counter()
            for _ in range(reps):
                o = fn(q, k, v)
            _ = float(jnp.asarray(o[0]).ravel()[0].astype(jnp.float32))
            return (time.perf_counter() - t0) / reps / R * 1e3

        # per-side try: a dense-side OOM (the motivating 4k failure) must
        # not discard the kernel-path number the tool exists to measure
        toks = int(lens.sum())
        row = {"T": t_len, "n": n, "valid_tokens_per_call": toks}
        try:
            tf_ = timeit(f_flash)
            row["flash_ms"] = round(tf_, 3)
            row["flash_tok_per_s"] = round(toks / tf_ * 1e3)
        except Exception as e:
            tf_ = None
            row["flash_error"] = f"{type(e).__name__}: {str(e)[:300]}"
        try:
            td_ = timeit(f_dense)
            row["dense_ms"] = round(td_, 3)
            row["dense_tok_per_s"] = round(toks / td_ * 1e3)
        except Exception as e:
            td_ = None
            row["dense_error"] = f"{type(e).__name__}: {str(e)[:300]}"
        if tf_ is not None and td_ is not None:
            row["speedup"] = round(td_ / tf_, 3)
        out["cases"].append(row)
        print(row, flush=True)

    write_unless_clobbering(path, out)


if __name__ == "__main__":
    main()
