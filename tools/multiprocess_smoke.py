"""Two-process multi-host smoke run of the distributed seam (VERDICT r3 #4).

The reference validates its driver/executor topology on a local-cluster
Spark master (SURVEY.md §4 "multi-node simulated locally"); this is the jax
analog: two OS processes on one machine, each owning 2 virtual CPU devices,
joined through ``Engine.init_distributed`` (jax.distributed coordinator) into
one 4-device cluster. The run asserts the global device view, executes a
cross-process psum, and trains a real model for one epoch through
``DistriOptimizer`` — whose collectives then genuinely cross the process
boundary.

Usage:
    python tools/multiprocess_smoke.py            # launcher: spawns 2 workers
    python tools/multiprocess_smoke.py --json     # also print artifact JSON

Exit code 0 + "MULTIPROC OK" on success. The launcher writes its JSON to
the path given with --artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_PROC = 2
DEVS_PER_PROC = 2


def _worker(process_id: int, port: int) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")

    sys.path.insert(0, REPO)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from bigdl_tpu.utils.engine import Engine

    Engine.init_distributed(
        coordinator_address=f"localhost:{port}",
        num_processes=N_PROC,
        process_id=process_id,
    )
    assert jax.process_count() == N_PROC, jax.process_count()
    n_global = len(jax.devices())
    n_local = len(jax.local_devices())
    assert n_global == N_PROC * DEVS_PER_PROC, n_global
    assert n_local == DEVS_PER_PROC, n_local
    mesh = Engine.mesh()
    assert mesh.devices.size == n_global

    # --- 1. a collective that must cross the process boundary ---
    @jax.jit
    def summed(x):
        return shard_map(
            lambda s: jax.lax.psum(s, "data"),
            mesh=mesh, in_specs=P("data"), out_specs=P(),
        )(x)

    glob = np.arange(n_global * 3, dtype=np.float32).reshape(n_global, 3)
    arr = jax.make_array_from_callback(
        glob.shape, jax.sharding.NamedSharding(mesh, P("data")),
        lambda idx: glob[idx],
    )
    got = np.asarray(summed(arr)).reshape(3)
    np.testing.assert_allclose(got, glob.sum(0), rtol=1e-6)
    print(f"[p{process_id}] psum across processes ok: {got.tolist()}",
          flush=True)

    # --- 2. one real DistriOptimizer epoch over the global mesh ---
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.parallel import DistriOptimizer
    from bigdl_tpu.utils.random import RandomGenerator

    RandomGenerator.set_seed(7)  # identical init on every process
    rng = np.random.default_rng(0)  # identical global data on every process
    xs = rng.standard_normal((64, 10)).astype(np.float32)
    w_true = rng.standard_normal((10, 4)).astype(np.float32)
    ys = np.argmax(xs @ w_true, axis=1)

    model = nn.Sequential(nn.Linear(10, 16), nn.ReLU(), nn.Linear(16, 4))
    ds = DataSet.distributed(DataSet.array(xs, ys, batch_size=16), n_global)
    opt = DistriOptimizer(model, ds, nn.CrossEntropyCriterion(),
                          parameter_sync="replicated")
    opt.set_optim_method(SGD(learningrate=0.5))
    opt.set_end_when(Trigger.max_epoch(8))
    opt.optimize()

    params = model.get_parameters()
    flat = np.concatenate([np.asarray(a).ravel()
                           for a in jax.tree_util.tree_leaves(params)])
    # training moved the params and every process holds identical values
    print(f"[p{process_id}] distri-optimizer epochs done; "
          f"param_checksum={float(np.sum(flat)):.6f}", flush=True)
    logits = model.forward(xs)
    acc = float((np.asarray(logits).argmax(1) == ys).mean())
    print(f"[p{process_id}] train acc={acc:.3f}", flush=True)
    assert acc > 0.9, f"distributed training failed to fit: acc={acc}"
    print(f"[p{process_id}] WORKER OK", flush=True)


def _launch(emit_json: bool, artifact: str | None) -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={DEVS_PER_PROC}")
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.time()
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--role", "worker", "--process-id", str(i),
             "--port", str(port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=REPO,
        )
        for i in range(N_PROC)
    ]
    outs = []
    ok = True
    for i, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            ok = False
        outs.append(out)
        if p.returncode != 0 or "WORKER OK" not in out:
            ok = False
    wall = time.time() - t0
    for i, out in enumerate(outs):
        interesting = [ln for ln in out.splitlines()
                       if "[p" in ln or "Error" in ln or "error" in ln]
        print(f"--- worker {i} ---")
        print("\n".join(interesting[-12:]))
    checksums = set()
    for out in outs:
        for ln in out.splitlines():
            if "param_checksum=" in ln:
                checksums.add(ln.split("param_checksum=")[1])
    if len(checksums) != 1:
        print(f"FAIL: divergent parameters across processes: {checksums}")
        ok = False
    result = {
        "ok": ok,
        "n_processes": N_PROC,
        "devices_per_process": DEVS_PER_PROC,
        "wall_s": round(wall, 1),
        "phases": [
            "jax.distributed join via Engine.init_distributed",
            "global 4-device mesh from 2 processes",
            "cross-process psum (shard_map)",
            "DistriOptimizer 8 epochs, replicated sync, acc>0.9",
            "identical post-training param checksum on both processes",
        ],
    }
    if emit_json:
        print(json.dumps(result))
    if artifact:
        with open(artifact, "w") as f:
            json.dump(result, f, indent=1)
    print("MULTIPROC OK" if ok else "MULTIPROC FAILED")
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", default="launcher")
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--artifact", default=None)
    args = ap.parse_args()
    if args.role == "worker":
        _worker(args.process_id, args.port)
        return 0
    return _launch(args.json, args.artifact)


if __name__ == "__main__":
    sys.exit(main())
