"""The table the flash kernel's tile rule rests on — device time by tile.

``ops/flash_attention.pick_tiles`` chooses (block_q, block_k) from shapes;
this measures what it should choose. For every shape in ``SHAPES`` and every
(block_q, block_k) in ``TILES``, plus the rule's own choice (``rule``): the
three kernels alone, forward and forward + backward, repeated inside one jit
(a ``fori_loop`` whose carry feeds each repetition's gradients back into its
operands, so nothing is hoisted or overlapped), timed on the host around
``block_until_ready``. One JSON line a row on stdout; the whole table to
``chiprun_out/flash_tile_table.json``. Needs the tpu backend.

    python tools/flash_tile_table.py            # every shape
    python tools/flash_tile_table.py a b        # the named rows only
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# (batch, query heads, K/V heads, T, head size, window, per-sequence lengths);
# "d_v": v's and the output's head size where it is not q's and k's
SHAPES = {
    "a": dict(n=2, h=32, hkv=4, t=8192, d=128, window=None, lengths=False),
    "b": dict(n=2, h=32, hkv=4, t=8192, d=128, window=1024, lengths=False),
    "c1": dict(n=2, h=4, hkv=4, t=1024, d=64, window=None, lengths=True),
    "c2": dict(n=2, h=4, hkv=4, t=4096, d=128, window=None, lengths=True),
    "d": dict(n=8, h=8, hkv=8, t=2048, d=64, window=None, lengths=False),
    "e": dict(n=2, h=32, hkv=32, t=8192, d=192, d_v=128, window=None,
              lengths=False),
}
TILES = [(bq, bk) for bq in (256, 512, 1024) for bk in (256, 512, 1024)]
TARGET_S = 0.25  # a timed call repeats the kernels until it lasts about this


def _functions(shape, bq, bk):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops.flash_attention import flash_attention

    n, h, hkv, t, d = (shape[key] for key in ("n", "h", "hkv", "t", "d"))
    d_v = shape.get("d_v", d)
    key = jax.random.PRNGKey(t + d)
    q, k, v, cot = (
        jax.random.normal(jax.random.fold_in(key, i), (n, heads, t, size),
                          jnp.bfloat16)
        for i, (heads, size) in enumerate(
            ((h, d), (hkv, d), (hkv, d_v), (h, d_v))))
    lens = None
    if shape["lengths"]:  # chip_smoke.check_flash's draw
        lens = jnp.asarray(
            np.random.default_rng(t).integers(t // 2, t + 1, n), jnp.int32)

    def attend(q, k, v):
        return flash_attention(q, k, v, True, block_q=bq, block_k=bk,
                               lengths=lens, window=shape["window"])

    def fwd(reps, q, k, v, cot):
        def body(_, q):
            out = attend(q, k, v)
            if d_v != d:  # the output back at q's head size: one pass more
                out = jnp.pad(out, ((0, 0),) * 3 + ((0, d - d_v),)) \
                    if d_v < d else out[..., :d]
            return q + 1e-6 * out
        return jax.lax.fori_loop(0, reps, body, q)

    # the cotangent is an argument, not a constant of the executable
    def fwd_bwd(reps, q, k, v, cot):
        def body(_, qkv):
            out, vjp = jax.vjp(attend, *qkv)
            return tuple(x + 1e-6 * g for x, g in zip(qkv, vjp(cot)))
        return jax.lax.fori_loop(0, reps, body, (q, k, v))

    return jax.jit(fwd), jax.jit(fwd_bwd), (q, k, v, cot)


def _ms_per_rep(fn, args) -> float:
    import jax

    def timed(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(reps, *args))
        return time.perf_counter() - t0

    timed(1)  # compile
    reps = max(2, min(2000, int(TARGET_S / max(timed(2) / 2, 1e-5))))
    return 1e3 * min(timed(reps) for _ in range(3)) / reps


def main(argv) -> None:
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"flash_tile_table measures the Mosaic kernels: needs the tpu "
            f"backend, found {jax.default_backend()!r}")
    from bigdl_tpu.ops.flash_attention import pick_tiles

    rows = []
    for name in (argv or list(SHAPES)):
        shape = SHAPES[name]
        rule = pick_tiles(shape["t"], shape["t"], shape["d"], 2,
                          shape.get("d_v"))
        for bq, bk in TILES + [(None, None)]:
            row = dict(shape=name, **shape, block_q=bq, block_k=bk,
                       rule=list(rule))
            try:
                fwd, fwd_bwd, args = _functions(shape, bq, bk)
                row["fwd_ms"] = round(_ms_per_rep(fwd, args), 4)
                row["fwd_bwd_ms"] = round(_ms_per_rep(fwd_bwd, args), 4)
            except Exception as e:  # a tile the compiler refuses is a row too
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(row), flush=True)
            rows.append(row)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "flash_tile_table.json"), "w") as f:
        json.dump({"device": str(jax.devices()[0]), "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
