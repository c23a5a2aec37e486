"""Maxpool-backward kernel vs XLA SelectAndScatter — device-time A/B.

In-jit repetition (R calls per compiled program) divides out the
per-dispatch latency, which otherwise swamps sub-10ms kernels; the scalar
pull at the end of each timed region is the sync. Writes
bench_artifacts/MAXPOOL_AB_r4.json.
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

    import bigdl_tpu.ops.maxpool as M

    from _bench_io import write_unless_clobbering

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"maxpool_ab times device kernels: needs the tpu backend, found "
            f"{jax.default_backend()!r}")
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "bench_artifacts", "MAXPOOL_AB_r4.json")

    R = 6
    cases = [
        ("resnet-stem 112->56 3x3/s2p1", (128, 64, 112, 112), (3, 3), (2, 2), ((1, 1), (1, 1))),
        ("incep-s1 28x28 3x3/s1p1", (128, 192, 28, 28), (3, 3), (1, 1), ((1, 1), (1, 1))),
        ("incep-s2 14->6 3x3/s2", (128, 480, 14, 14), (3, 3), (2, 2), ((0, 0), (0, 0))),
        ("vgg 2x2/s2 32x32", (128, 128, 32, 32), (2, 2), (2, 2), ((0, 0), (0, 0))),
    ]
    rng = np.random.default_rng(0)
    wx = jnp.ones((1024, 1024), jnp.float32)
    warm = jax.jit(lambda t: (t @ t).sum())
    for _ in range(3):
        _ = float(warm(wx))

    out = {"R_in_jit": R, "device": str(jax.devices()[0]), "cases": []}
    for name, shape, k, s, pad in cases:
        n, c, h, w = shape
        kh, kw = k
        sh, sw = s
        (pl_, ph_), (pw_, pr_) = pad
        ho = (h + pl_ + ph_ - kh) // sh + 1
        wo = (w + pw_ + pr_ - kw) // sw + 1
        x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        dy = jnp.asarray(rng.standard_normal((n, c, ho, wo)), jnp.float32)

        def many(which):
            def f(x, dy):
                acc = jnp.zeros_like(x)
                for i in range(R):
                    xi = x + i * 0.001
                    if which == "pallas":
                        acc = acc + M._maxpool_grad_nchw(
                            xi, dy, k, s, (pl_, pw_), (ho, wo))
                    elif which == "shift":
                        acc = acc + M.maxpool_grad_shift(xi, dy, k, s, pad)
                    else:
                        acc = acc + M.maxpool_grad_reference(xi, dy, k, s, pad)
                return acc
            return jax.jit(f)

        def timeit(fn, reps=8):
            fn(x, dy)
            o = fn(x, dy)
            _ = float(o[0, 0, 0, 0])
            t0 = time.perf_counter()
            for _ in range(reps):
                o = fn(x, dy)
            _ = float(o[0, 0, 0, 0])
            return (time.perf_counter() - t0) / reps / R * 1e3

        tx = timeit(many("xla"))
        ts_ = timeit(many("shift"))
        err_s = float(jnp.abs(
            M.maxpool_grad_shift(x, dy, k, s, pad)
            - M.maxpool_grad_reference(x, dy, k, s, pad)).max())
        err = float(jnp.abs(
            M._maxpool_grad_nchw(x, dy, k, s, (pl_, pw_), (ho, wo))
            - M.maxpool_grad_reference(x, dy, k, s, pad)).max())
        tp = timeit(many("pallas"))
        row = {"case": name, "xla_ms": round(tx, 3),
               "shift_ms": round(ts_, 3), "shift_max_abs_diff": err_s,
               "shift_speedup_vs_xla": round(tx / ts_, 3),
               "max_abs_diff": err, "pallas_ms": round(tp, 3),
               "speedup_vs_xla": round(tx / tp, 3)}
        out["cases"].append(row)
        print(row, flush=True)

    write_unless_clobbering(path, out)


if __name__ == "__main__":
    main()
