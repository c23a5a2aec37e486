"""The table the flash kernel's tile rule rests on — device time by tile.

``ops/flash_attention.pick_tiles`` chooses (block_q, block_k) from shapes;
this measures what it should choose. For every shape in ``SHAPES`` and every
(block_q, block_k) in ``TILES``, plus the rule's own choice (``rule``): the
kernels alone, forward and forward + backward, repeated inside one jit
(a ``fori_loop`` whose carry feeds each repetition's gradients back into its
operands, so nothing is hoisted or overlapped), timed on the host around
``block_until_ready``. For the shapes in ``BACKWARD`` also the backward
alone in both of its forms, off one forward's output and logsumexp: the one
kernel (``bwd_fused_ms``) and the pair (``bwd_pair_ms``); which of them a
shape gets is ``ops/flash_attention.backward_form``'s choice, and
``fwd_bwd_ms`` runs that one. One JSON line a row on stdout; the whole table
to ``chiprun_out/flash_tile_table.json``. Needs the tpu backend.

    python tools/flash_tile_table.py            # every shape
    python tools/flash_tile_table.py a b        # the named rows only
    python tools/flash_tile_table.py a e --tiles 1024x1024,512x512
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
    "g": dict(n=1, h=32, hkv=8, t=8192, d=64, window=None, lengths=False),
}
# the cells' shapes (M full and windowed, J, G): the backward in both forms
BACKWARD = ("a", "b", "e", "g")
TILES = [(bq, bk) for bq in (256, 512, 1024) for bk in (256, 512, 1024)]
TARGET_S = 0.25  # a timed call repeats the kernels until it lasts about this


def _functions(shape, bq, bk):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops.flash_attention import (
        _flash_bwd_impl, _flash_fwd_impl, flash_attention, pick_tiles)

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

    # the backward alone, in the form asked for, at this tile (the rule's
    # where none is given), off a forward at the same tile
    tile = (bq, bk) if bq else pick_tiles(t, t, d, 2, d_v)

    def residuals(q, k, v):
        return _flash_fwd_impl(q, k, v, lens, True, None, *tile, False, True,
                               shape["window"])

    def bwd(fused):
        def run(reps, q, k, v, cot, out, lse):
            def body(_, qkv):
                grads = _flash_bwd_impl(
                    *qkv, lens, out, lse, cot, True, None, *tile, False, True,
                    shape["window"], fused)
                return tuple(x + 1e-6 * g for x, g in zip(qkv, grads))
            return jax.lax.fori_loop(0, reps, body, (q, k, v))
        return jax.jit(run)

    return jax.jit(fwd), jax.jit(fwd_bwd), (q, k, v, cot), \
        jax.jit(residuals), bwd


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
    from bigdl_tpu.ops.flash_attention import backward_form, pick_tiles

    tiles = TILES
    if "--tiles" in argv:
        at = argv.index("--tiles")
        tiles = [tuple(int(b) for b in pair.split("x"))
                 for pair in argv[at + 1].split(",")]
        argv = argv[:at] + argv[at + 2:]
    rows = []
    for name in (argv or list(SHAPES)):
        shape = SHAPES[name]
        d_v = shape.get("d_v")
        rule = pick_tiles(shape["t"], shape["t"], shape["d"], 2, d_v)
        for bq, bk in tiles + [(None, None)]:
            fused, acc = backward_form(
                shape["t"], bq or rule[0], bk or rule[1], shape["d"], 2, d_v)
            row = dict(shape=name, **shape, block_q=bq, block_k=bk,
                       rule=list(rule), backward="fused" if fused else "pair",
                       backward_acc_bytes=acc)
            try:
                fwd, fwd_bwd, args, residuals, bwd = _functions(shape, bq, bk)
                row["fwd_ms"] = round(_ms_per_rep(fwd, args), 4)
                row["fwd_bwd_ms"] = round(_ms_per_rep(fwd_bwd, args), 4)
                if name in BACKWARD:
                    both = args + tuple(residuals(*args[:3]))
                    for form, key in ((True, "bwd_fused_ms"),
                                      (False, "bwd_pair_ms")):
                        if form and not fused:
                            continue  # the accumulator does not fit
                        row[key] = round(_ms_per_rep(bwd(form), both), 4)
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
