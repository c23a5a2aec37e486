"""Capture a jax.profiler trace of one parity-config train step on-chip
and summarize device time by XLA op category.

The r3 ResNet trace analysis (bench_artifacts/TRACE_ANALYSIS_r3.md) is the
model: it attributed 20% of Inception's step to maxpool backward
(SelectAndScatter) and motivated the Pallas kernel. This trace is the
evidence for whether ~0.20 MFU is Inception's v5e roofline (round-4 review
item): if the step is HBM-bound with SelectAndScatter a fixed slice, the tax
is architectural until a kernel beats it.

    python tools/trace_config.py inception [--steps 4]
"""

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("config", nargs="?", default="inception")
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()

    from functools import partial

    import jax
    import jax.numpy as jnp

    import bench
    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator
    from trace_summary import summarize

    RandomGenerator.set_seed(1)
    Engine.set_compute_dtype(os.environ.get("BENCH_COMPUTE_DTYPE", "bfloat16"))
    act = os.environ.get("BENCH_ACT_DTYPE", "bfloat16")
    if act != "float32":
        Engine.set_activation_dtype(act)

    model, x, t, batch = bench._parity_config(args.config)
    criterion = nn.ClassNLLCriterion()
    method = SGD(learningrate=0.01, momentum=0.9)
    params, state = model.init(sample_input=x)
    slots = method.init_slots(params)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, state, slots, x, t, rng):
        def loss_fn(p):
            y, s = model.apply(p, state, x, training=True, rng=rng)
            return criterion._apply(y, t), s

        (loss, new_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        params, slots = method.update(
            grads, params, slots, jnp.asarray(0.01), jnp.asarray(1))
        return params, new_state, slots, loss

    xs = jax.tree_util.tree_map(jnp.asarray, x)
    ts = jnp.asarray(t)
    rng = jax.random.PRNGKey(0)
    for _ in range(3):
        params, state, slots, loss = train_step(params, state, slots,
                                                xs, ts, rng)
    float(loss)

    tdir = tempfile.mkdtemp(prefix=f"trace_{args.config}_")
    jax.profiler.start_trace(tdir)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        params, state, slots, loss = train_step(params, state, slots,
                                                xs, ts, rng)
    float(loss)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()

    traces = glob.glob(os.path.join(tdir, "**", "*.trace.json.gz"),
                       recursive=True)
    if not traces:
        print(json.dumps({"error": f"no trace written under {tdir}"}))
        return
    rows = summarize(traces[0], args.steps)
    out = {
        "config": args.config,
        "batch": batch,
        "steps_traced": args.steps,
        "wall_ms_per_step": round(wall / args.steps * 1e3, 2),
        "device": str(jax.devices()[0]),
        "trace_path": traces[0],
        "by_category": rows,
    }
    art = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "bench_artifacts", f"TRACE_{args.config}_r5.json")
    with open(art, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in out if k != "by_category"}))
    for r in rows:
        print(r)
    print("wrote", art)


if __name__ == "__main__":
    main()
