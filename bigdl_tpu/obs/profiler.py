"""One-shot model introspection: per-layer HBM breakdown + HLO cost summary.

Where :mod:`~bigdl_tpu.obs.health` streams per-step statistics, this module
answers the STATIC half of "why is the model unhealthy": where the HBM goes
(per-layer parameter and optimizer-slot bytes, per-shard for the ZeRO-1 flat
layout and GSPMD-committed arrays) and what one train step costs
(FLOPs / bytes accessed via ``compiled.cost_analysis()``).

Everything here is one-shot and host-side: byte counts come from
shapes/dtypes and committed shardings (``sharding.shard_shape`` — a metadata
read, never a device sync), and the cost summary lowers+compiles the step
once, outside the training loop. ``tools/health_report.py`` is the CLI
front-end; ``profile_optimizer`` is the library entry point.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import numpy as np

from .health import flat_leaf_path, pretty_path

__all__ = [
    "memory_breakdown",
    "flat_memory_breakdown",
    "cost_summary",
    "lowered_cost_summary",
    "collective_bytes",
    "profile_optimizer",
]


def _leaf_bytes(leaf) -> int:
    """Bytes of one array/spec from shape x itemsize (works for concrete
    arrays and ShapeDtypeStructs alike — no data touched)."""
    shape = getattr(leaf, "shape", ())
    dtype = np.dtype(getattr(leaf, "dtype", np.float32))
    return int(np.prod(shape, dtype=np.int64)) * dtype.itemsize if shape else dtype.itemsize


def _shard_bytes(leaf) -> Optional[int]:
    """Per-device bytes of a COMMITTED sharded array (metadata only); None
    for uncommitted/replicated-by-default leaves."""
    sharding = getattr(leaf, "sharding", None)
    if sharding is None or not getattr(leaf, "_committed", False):
        return None
    shard_shape = getattr(sharding, "shard_shape", None)
    if shard_shape is None:
        return None
    try:
        shp = shard_shape(tuple(leaf.shape))
    except (TypeError, ValueError):
        return None
    dtype = np.dtype(leaf.dtype)
    return int(np.prod(shp, dtype=np.int64)) * dtype.itemsize


# layer names in the memory tables come from the same helpers the health
# records use (obs/health.py) — the two views join on these paths
_pretty = pretty_path


def memory_breakdown(params, slots=None) -> Dict[str, Any]:
    """Per-layer parameter + optimizer-slot byte table for TREE layouts
    (local / replicated / GSPMD).

    ``slots`` is an optimizer slot pytree whose top level names the slot
    (``{"velocity": <param-tree>}``, ``{"m": ..., "v": ...}``); each slot
    subtree mirrors the parameter tree, so slot leaves attribute back to
    their layer by sub-path. Committed GSPMD leaves additionally report
    ``param_shard_bytes`` / ``slot_shard_bytes`` — the per-device resident
    size under the committed NamedSharding."""
    import jax

    layers: Dict[str, Dict[str, Any]] = {}
    total_p = total_s = 0
    sharded = False
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        b = _leaf_bytes(leaf)
        entry = layers.setdefault(
            _pretty(path), {"param_bytes": 0, "slot_bytes": 0}
        )
        entry["param_bytes"] += b
        total_p += b
        sb = _shard_bytes(leaf)
        if sb is not None and sb != b:
            entry["param_shard_bytes"] = entry.get("param_shard_bytes", 0) + sb
            sharded = True
    if slots:
        for path, leaf in jax.tree_util.tree_flatten_with_path(slots)[0]:
            b = _leaf_bytes(leaf)
            # ['velocity']['Linear_0']['weight'] -> layer Linear_0/weight
            layer = _pretty(path[1:]) if len(path) > 1 else _pretty(path)
            entry = layers.setdefault(
                layer, {"param_bytes": 0, "slot_bytes": 0}
            )
            entry["slot_bytes"] += b
            total_s += b
            sb = _shard_bytes(leaf)
            if sb is not None and sb != b:
                entry["slot_shard_bytes"] = entry.get("slot_shard_bytes", 0) + sb
                sharded = True
    return {
        "layout": "gspmd" if sharded else "tree",
        "layers": layers,
        "totals": {
            "param_bytes": total_p,
            "slot_bytes": total_s,
            "total_bytes": total_p + total_s,
        },
    }


def flat_memory_breakdown(fp, method=None) -> Dict[str, Any]:
    """Per-layer byte table for the flat master-state layout (DistriOptimizer
    ``parameter_sync='sharded'``).

    The padded f32 flat vector is the CARRIED master buffer (donated each
    step, the all-gather/update aliases into it — ``totals.master_bytes``);
    the per-layer tree exists as slice views inside the step plus the entry
    tree the model object still references (``param_bytes``, counted at the
    tree dtypes — stale after step 0 but resident until the run's cold seams
    re-materialize it). Optimizer slots live as f32 flat vectors SHARDED
    across devices (``shard_size`` elements per device per slot vector).
    ``fp`` is the
    :class:`~bigdl_tpu.parallel.parameter.FlatParameter` codec; ``method``
    (when given) determines the slot-vector count by initializing slots on
    an abstract flat spec."""
    n_slot_vecs = 0
    if method is not None:
        import jax
        import jax.numpy as jnp

        slots_spec = jax.eval_shape(
            method.init_slots,
            jax.ShapeDtypeStruct((fp.padded_total,), jnp.float32),
        )
        n_slot_vecs = len(jax.tree_util.tree_leaves(slots_spec))
    layers: Dict[str, Dict[str, Any]] = {}
    for raw_path, size, dtype in zip(fp.paths, fp.sizes, fp.dtypes):
        path = flat_leaf_path(raw_path)
        param_b = size * np.dtype(dtype).itemsize
        layers[path] = {
            "param_bytes": param_b,
            # this layer's share of each sharded f32 slot vector, summed
            "slot_bytes": size * 4 * n_slot_vecs,
        }
    shard_b = fp.shard_size * 4
    master_b = fp.padded_total * 4
    param_b = sum(e["param_bytes"] for e in layers.values())
    slot_b = fp.padded_total * 4 * n_slot_vecs
    return {
        "layout": "flat_zero1",
        "layers": layers,
        "totals": {
            "param_bytes": param_b,
            "slot_bytes": slot_b,
            # the carried flat f32 master vector — the canonical, donated
            # training state (the tree is a view/seam materialization)
            "master_bytes": master_b,
            "total_bytes": param_b + slot_b + master_b,
        },
        "flat": {
            "n_shards": fp.n_shards,
            "shard_size": fp.shard_size,
            "padded_total": fp.padded_total,
            "flat_vector_bytes": master_b,  # legacy alias of master_bytes
            "master_vector_bytes": master_b,
            "master_carried": True,  # donated in place each step, no shadow
            "slot_vectors": n_slot_vecs,
            # what ONE device holds of the sharded optimizer state
            "slot_shard_bytes_per_device": shard_b * n_slot_vecs,
        },
    }


def cost_summary(jit_fn, *args, **kwargs) -> Optional[Dict[str, Any]]:
    """FLOPs / bytes-accessed of one compiled call via
    ``lowered.compile().cost_analysis()``. ``args`` may be concrete arrays or
    ``ShapeDtypeStruct``s (nothing executes — lower+compile only; with the
    persistent compile cache enabled the compile is usually a disk hit).
    Returns None when the backend reports no cost model."""
    compiled = jit_fn.lower(*args, **kwargs).compile()
    try:
        cost = compiled.cost_analysis()
    except NotImplementedError:  # backend without a cost model
        return None
    return _parse_cost(cost)


def lowered_cost_summary(lowered) -> Optional[Dict[str, Any]]:
    """Cost summary of an ALREADY-lowered program — the always-on perf
    accounting seam (``obs/perf.py`` calls this once per compiled step).

    Prefers ``lowered.cost_analysis()`` (the pre-compile HLO cost analysis
    — no second XLA compile, so the accounting adds only a lowering to each
    fit) and falls back to ``lowered.compile().cost_analysis()`` — the
    sanctioned compiled seam ``cost_summary`` uses, a persistent-cache disk
    hit when a cache dir is configured. Returns None when neither path
    reports a cost model."""
    cost = None
    try:
        cost = lowered.cost_analysis()
    except Exception:
        cost = None  # no pre-compile cost model here: try the compiled path
    parsed = _parse_cost(cost)
    if parsed is not None:
        return parsed
    try:
        cost = lowered.compile().cost_analysis()
    except Exception as e:  # no cost model / refused compile: degrade
        import logging

        logging.getLogger("bigdl_tpu.obs").debug(
            "lowered_cost_summary: compiled cost analysis unavailable (%s)", e
        )
        return None
    return _parse_cost(cost)


def _parse_cost(cost) -> Optional[Dict[str, Any]]:
    """Normalize an XLA cost-analysis dict into the summary schema shared
    by ``cost_summary`` and ``lowered_cost_summary``."""
    if not cost:
        return None
    flops = float(cost.get("flops", 0.0)) or None
    raw_bytes = cost.get("bytes accessed")
    bytes_accessed = float(raw_bytes) if raw_bytes is not None else None
    out: Dict[str, Any] = {
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "arithmetic_intensity": (
            round(flops / bytes_accessed, 3)
            if flops and bytes_accessed
            else None
        ),
    }
    # per-memory-space traffic (bytes accessed0{} = HBM on TPU) when present
    spaces = {
        k: float(v)
        for k, v in cost.items()
        if k.startswith("bytes accessed") and k != "bytes accessed"
    }
    if spaces:
        out["bytes_accessed_by_space"] = spaces
    return out


# ---------------------------------------------------------------------------
# collective operand bytes (the low-precision comms lock, docs/performance.md)
# ---------------------------------------------------------------------------

# StableHLO collective ops and how their operand relates to what one device
# puts on the wire: for every one of these the OPERAND is exactly the
# per-device send buffer, so "operand bytes" = wire bytes per device per step
_COLLECTIVE_OPS = (
    "all_reduce", "reduce_scatter", "all_gather", "all_to_all",
    "collective_permute",
)

_TENSOR_RE = None  # compiled lazily (module imports stay numpy-only)


def _stablehlo_tensor_bytes(type_text: str) -> int:
    """Total bytes of every ``tensor<...>`` in an MLIR type list, e.g.
    ``(tensor<8x8xf8E4M3FN>, tensor<4xf32>)``."""
    import re

    global _TENSOR_RE
    if _TENSOR_RE is None:
        _TENSOR_RE = re.compile(r"tensor<((?:\d+x)*)([a-zA-Z][a-zA-Z0-9]*)>")
    total = 0
    for dims, dtype in _TENSOR_RE.findall(type_text):
        n = 1
        for d in dims.split("x"):
            if d:
                n *= int(d)
        if dtype.startswith("f8"):
            bits = 8
        elif dtype == "bf16":
            bits = 16
        elif dtype.startswith("f"):
            bits = int(dtype[1:])
        elif dtype.startswith("ui"):
            bits = max(int(dtype[2:]), 8)
        elif dtype.startswith("i"):
            bits = max(int(dtype[1:]), 8)
        else:  # unknown element type: count conservatively as 4 bytes
            bits = 32
        total += n * (bits // 8)
    return total


def collective_bytes(lowered) -> Dict[str, Any]:
    """Per-device collective OPERAND bytes of a lowered program — the bytes
    each device puts on the interconnect per step, by op kind. This is the
    measurement behind the compressed-comms lock: ``grad_exchange_bytes``
    (reduce_scatter + all_to_all — the gradient aggregation ops) must drop
    ≥2× under ``comms_dtype='bfloat16'`` and ≥3.5–4× under fp8/int8 versus
    the f32 baseline, while the default-policy program stays byte-for-byte
    unchanged (docs/performance.md "reading the all-reduce-bytes lock").

    ``lowered`` is a ``jit(...).lower(...)`` result or its ``as_text()``
    StableHLO string. Pure text analysis — nothing compiles or executes."""
    text = lowered if isinstance(lowered, str) else lowered.as_text()
    lines = text.splitlines()
    ops = []
    for i, line in enumerate(lines):
        hit = next(
            (op for op in _COLLECTIVE_OPS if f'"stablehlo.{op}"' in line), None
        )
        if hit is None:
            continue
        # the operand/result signature is on the op line for region-free ops
        # (all_gather/all_to_all/collective_permute) and on the region-closing
        # ``}) : (tensor<...>) -> ...`` line for all_reduce/reduce_scatter
        sig = None
        for j in range(i, min(i + 64, len(lines))):
            cand = lines[j]
            if ") -> " in cand and "tensor<" in cand:
                sig = cand
                break
        if sig is None:
            continue
        operand_text = sig.rsplit(") -> ", 1)[0]
        operand_text = operand_text[operand_text.rfind(": (") :]
        ops.append({"op": hit, "operand_bytes": _stablehlo_tensor_bytes(operand_text)})
    by_op: Dict[str, int] = {}
    for rec in ops:
        by_op[rec["op"]] = by_op.get(rec["op"], 0) + rec["operand_bytes"]
    return {
        "ops": ops,
        "by_op": by_op,
        "grad_exchange_bytes": (
            by_op.get("reduce_scatter", 0) + by_op.get("all_to_all", 0)
        ),
        "all_reduce_bytes": by_op.get("all_reduce", 0),
        "all_gather_bytes": by_op.get("all_gather", 0),
        # the pp/ep classification: expert-dispatch bytes (the two MoE
        # all_to_all hops) and pipeline ring-shift bytes (ppermute lowers to
        # collective_permute) broken out of the grad-exchange aggregate so
        # the comms decomposition can name the parallelism that paid them
        "all_to_all_bytes": by_op.get("all_to_all", 0),
        "ppermute_bytes": by_op.get("collective_permute", 0),
        "total_bytes": sum(by_op.values()),
    }


def profile_optimizer(opt, cost: bool = True) -> Dict[str, Any]:
    """One-shot health profile of an optimizer's training setup: builds the
    model from the dataset spec when needed, then reports the per-layer
    HBM breakdown (flat ZeRO-1 geometry for a sharded DistriOptimizer, the
    tree layout otherwise) and — for the tree-step paths — the HLO cost of
    one train step (``cost=False`` skips the lower+compile).

    Runs OUTSIDE the training loop: nothing here dispatches a step or syncs
    the device."""
    import jax

    from ..parallel.distri_optimizer import DistriOptimizer
    from ..parallel.parameter import FlatParameter
    from ..utils.engine import Engine

    if not opt.model.is_built():
        opt._build_for_resume()  # the shared build-from-dataset-spec seam
    params = opt.model.get_parameters()
    method = opt.optim_method
    n_params = sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params)
    )
    out: Dict[str, Any] = {
        "path": type(opt).__name__,
        "n_params": n_params,
    }

    flat_sharded = False
    if isinstance(opt, DistriOptimizer):
        n_dev = Engine.mesh().devices.size
        # same resolution the training path runs — the reported layout is
        # the layout optimize() would actually pick
        sync = opt._resolve_parameter_sync(method, params)
        flat_sharded = sync == "sharded"
        out["parameter_sync"] = sync
    if flat_sharded:
        fp = FlatParameter(params, n_dev)
        out["memory"] = flat_memory_breakdown(fp, method)
    else:
        slots_spec = jax.eval_shape(method.init_slots, params)
        out["memory"] = memory_breakdown(params, slots_spec)

    out["cost"] = None
    if cost and not isinstance(opt, DistriOptimizer):
        # tree-step paths (Local / HybridParallel): lower the actual cached
        # train step against abstract specs of the first batch
        first = next(iter(opt.dataset.data(train=True)), None)
        if first is not None:
            import jax.numpy as jnp

            spec = jax.eval_shape
            x = spec(lambda: _as_jnp(first.get_input()))
            t = spec(lambda: _as_jnp(first.get_target()))
            params_spec = spec(lambda: _as_jnp(params))
            step = opt._cached_standard_step(method)
            scalar = jax.ShapeDtypeStruct((), jnp.float32)
            out["cost"] = cost_summary(
                step,
                params_spec,
                spec(lambda: _as_jnp(opt.model.get_state())),
                spec(method.init_slots, params_spec),  # abstract: no alloc
                x,
                t,
                scalar,                                    # nvalid
                scalar,                                    # lr
                jax.ShapeDtypeStruct((), jnp.int32),       # step
                jax.ShapeDtypeStruct((2,), jnp.uint32),    # rng key
            )
    return out


def _as_jnp(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.asarray, tree)


def render_memory(report: Dict[str, Any], top: int = 0) -> str:
    """Human table for a ``memory_breakdown``/``flat_memory_breakdown``
    result (``tools/health_report.py`` output)."""
    lines = []
    layers = report["layers"]
    rows = sorted(
        layers.items(),
        key=lambda kv: -(kv[1]["param_bytes"] + kv[1]["slot_bytes"]),
    )
    shown = rows[:top] if top else rows
    width = max((len(p) for p, _ in shown), default=10)
    for path, e in shown:
        extra = ""
        if "param_shard_bytes" in e or "slot_shard_bytes" in e:
            extra = "  per-shard %s" % _fmt_bytes(
                e.get("param_shard_bytes", 0) + e.get("slot_shard_bytes", 0)
            )
        lines.append(
            f"  {path:<{width}}  params {_fmt_bytes(e['param_bytes']):>10}  "
            f"slots {_fmt_bytes(e['slot_bytes']):>10}{extra}"
        )
    if top and len(rows) > top:
        lines.append(f"  ... {len(rows) - top} more layers")
    t = report["totals"]
    lines.append(
        f"  {'TOTAL':<{width}}  params {_fmt_bytes(t['param_bytes']):>10}  "
        f"slots {_fmt_bytes(t['slot_bytes']):>10}"
    )
    flat = report.get("flat")
    if flat:
        lines.append(
            "  flat ZeRO-1: %d shards x %s flat-vector slice; %s of sharded "
            "slot state per device (%d slot vector(s))"
            % (
                flat["n_shards"],
                _fmt_bytes(flat["shard_size"] * 4),
                _fmt_bytes(flat["slot_shard_bytes_per_device"]),
                flat["slot_vectors"],
            )
        )
        if flat.get("master_carried"):
            lines.append(
                "  master: %s carried flat f32 vector (donated in place each "
                "step; the tree is an in-step view, materialized only at "
                "checkpoint/validation seams)"
                % _fmt_bytes(flat.get("master_vector_bytes", 0))
            )
    return "\n".join(lines)


def _fmt_bytes(n: float) -> str:
    if not n:
        return "0"
    units = ("B", "KiB", "MiB", "GiB", "TiB")
    i = min(int(math.log(abs(n), 1024)), len(units) - 1)
    return f"{n / 1024 ** i:.1f}{units[i]}"
