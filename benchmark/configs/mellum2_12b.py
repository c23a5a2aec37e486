"""Mellum2-12B-A2.5B-Instruct, one chip's share of a 4-way expert-parallel
deployment, through ``LocalOptimizer.optimize()``: the model is
``bigdl_tpu.models.decoder_lm.from_config`` of the configuration's JSON, whose
keys are the model's public ``config.json`` keys.

Three things live here beside ``build``:

* the forward pass **in counting form** (``forward``): a function of
  ``dot_general``s only, whose shapes are exactly the forward work the
  equations need (``lib/flops.py`` walks ``dot_general``, and would count one
  tile of a Pallas kernel and no grouped product at all);
* the operations and least bytes of the two kernels (``attention_cost``,
  ``experts_cost``), which the roofline readers take from ``run.forward``;
* ``compare``: the comparison with the float32 reference
  (``mellum2_12b_reference.py``, the benchmark's own copy) that driver
  ``train_ref`` ANDs into ``correct``.
"""

from __future__ import annotations

from types import SimpleNamespace

BF16, F32 = 2, 4


def model_config(cfg: dict) -> dict:
    """The builder's dict: the JSON's keys, with ``num_experts`` back at the
    router's width (the file counts the experts HELD under that key, as the
    cut asks; ``experts_held`` names them)."""
    return {**cfg, "num_experts": int(cfg["router_width"])}


def _layer_kinds(cfg: dict):
    return list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]


def visible_pairs(t: int, window=None) -> int:
    """(query, key) pairs a causal layer sees over one sequence and head."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def products(cfg: dict, records: int):
    """(name, m, k, n) of every matrix product of one forward pass over
    ``records`` records: the work the equations need, no masked tile, no
    recomputation, experts over the expected pairs of the experts held."""
    t = int(cfg["deployment"]["record_tokens"])
    rows = records * t
    d_model, d = int(cfg["hidden_size"]), int(cfg["head_dim"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    f = int(cfg["moe_intermediate_size"])
    held, width = len(cfg["experts_held"]), int(cfg["router_width"])
    pairs = rows * int(cfg["num_experts_per_tok"]) * held // width
    out = []
    for i, kind in enumerate(_layer_kinds(cfg)):
        window = int(cfg["sliding_window"]) if kind == "sliding_attention" else None
        seen = records * hq * visible_pairs(t, window)
        out += [(f"l{i}.wq", rows, d_model, hq * d),
                (f"l{i}.wk", rows, d_model, hkv * d),
                (f"l{i}.wv", rows, d_model, hkv * d),
                (f"l{i}.qk", seen, d, 1),       # 2 d FLOPs a visible pair
                (f"l{i}.pv", d, seen, 1),       # and 2 d more
                (f"l{i}.wo", rows, hq * d, d_model),
                (f"l{i}.router", rows, d_model, width),
                (f"l{i}.w_gate", pairs, d_model, f),
                (f"l{i}.w_up", pairs, d_model, f),
                (f"l{i}.w_down", pairs, f, d_model)]
    out.append(("head", rows, d_model, int(cfg["vocab_size"])))
    return out


def attention_cost(cfg: dict, records: int, kind: str):
    """(FLOPs, least bytes) of one layer's Q.K^T and P.V over the visible
    pairs: q and the output once each (Hq heads), k and v once (Hkv heads),
    all in the compute dtype."""
    t = int(cfg["deployment"]["record_tokens"])
    d = int(cfg["head_dim"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    window = int(cfg["sliding_window"]) if kind == "sliding_attention" else None
    flops = 4.0 * d * records * hq * visible_pairs(t, window)
    return flops, float(records * t * d * 2 * (hq + hkv) * BF16)


def experts_cost(cfg: dict, pairs: float, layers: int):
    """(FLOPs, least bytes) of the three grouped products over ``pairs``
    routed pairs in all (``layers`` layers together): each held expert's
    three matrices once a layer, the pairs' rows in and out once each
    (operands in the compute dtype, results float32)."""
    d_model, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    held = len(cfg["experts_held"])
    flops = pairs * 3 * 2.0 * d_model * f
    weights = layers * held * 3 * d_model * f * BF16
    rows = pairs * (d_model * BF16 + 2 * f * F32 + f * BF16 + d_model * F32)
    return flops, float(weights + rows)


def build(cfg: dict, traffic, seed: int, chips: int) -> dict:
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.models import decoder_lm
    from bigdl_tpu.optim import Adam, Default, LinearWarmup, LocalOptimizer
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    if chips != 1:
        raise ValueError(f"{cfg['name']}: LocalOptimizer drives one chip, "
                         f"the cell asks for {chips}")
    RandomGenerator.set_seed(seed)
    Engine.set_compute_dtype(cfg["dtypes"]["compute"])
    Engine.set_activation_dtype(cfg["dtypes"]["activation"])
    model = decoder_lm.from_config(model_config(cfg))
    opt = LocalOptimizer(model, traffic.dataset, nn.TokenCrossEntropyCriterion())
    o = cfg["optimizer"]
    method = Adam(learningrate=o["learning_rate"], beta1=o["beta1"],
                  beta2=o["beta2"], epsilon=o["epsilon"])
    method.schedule = LinearWarmup(int(o["warmup_steps"]), Default())
    opt.set_optim_method(method)

    def forward():
        """(fn, args) of one step's forward pass in counting form."""
        shapes = [(m, k, n) for _, m, k, n in products(cfg, traffic.batch)]
        args = [jax.ShapeDtypeStruct(s, jnp.bfloat16)
                for m, k, n in shapes for s in ((m, k), (k, n))]
        return (lambda *a: [jnp.dot(x, w) for x, w in zip(a[::2], a[1::2])],
                args)

    forward.attention_cost = lambda kind: attention_cost(cfg, traffic.batch, kind)
    forward.experts_cost = lambda pairs: experts_cost(
        cfg, pairs, int(cfg["num_hidden_layers"]))
    forward.layer_kinds = _layer_kinds(cfg)
    return {"optimizer": opt, "forward": forward}


# --------------------------------------------------------------------------
# the comparison with the reference
# --------------------------------------------------------------------------

def _reference():
    """The benchmark's own copy of the reference, loaded as the harness
    loads every file: by name, from this directory's root."""
    from benchmark import run as bench

    return bench.load_module("configs", "mellum2_12b_reference", (bench.HERE,))


def _seeded_optimizer(cfg: dict, x, y, seed: int):
    """``build``'s optimizer over the one batch, its model built from
    ``seed``."""
    import jax
    import numpy as np

    from bigdl_tpu.dataset import DataSet

    n = x.shape[0]
    opt = build(cfg, SimpleNamespace(
        dataset=DataSet.array(np.asarray(x), np.asarray(y), batch_size=n),
        batch=n), seed, 1)["optimizer"]
    opt.model.build(jax.random.PRNGKey(seed % (2**31)),
                    jax.ShapeDtypeStruct(x.shape, x.dtype))
    return opt


def seeded_parameters(cfg: dict, x, seed: int):
    """The seeded weights the comparison runs on."""
    return _seeded_optimizer(cfg, x, x, seed).model.get_parameters()


def system_loss_and_grad(cfg: dict, x, y, at, seed: int):
    """Seeded weights, and the system's loss, gradients, counters and logits
    at the positions ``at`` (N, m) on one batch, from the function the train
    step differentiates: the optimizer's own ``_loss_fn`` over the same
    module, criterion, dtype policy and kernels, jitted at these shapes. The
    logits leave through a forward hook on the head (the state pytree is the
    step's side channel), so it is one pass and one compile."""
    import jax
    import jax.numpy as jnp

    opt = _seeded_optimizer(cfg, x, y, seed)
    model = opt.model
    rows = jnp.arange(x.shape[0])[:, None]
    model.modules[-1].register_forward_hook(
        lambda module, inp, out: {"_picked": out[rows, at]})
    params, state = model.get_parameters(), model.get_state()
    (loss, new_state), grads = jax.jit(jax.value_and_grad(
        opt._loss_fn, has_aux=True))(params, state, x, y, jax.random.PRNGKey(0))
    picked = new_state[model.modules[-1].name()]["_picked"]
    counters = {k: float(v) for k, v in model.counters_tree(new_state).items()}
    return params, float(loss), grads, picked, counters


def compare(cfg: dict, mix: dict, generator, seed: int, log,
            block_q: int = 512, stand_in: dict = None) -> bool:
    """The system against the reference AT THE STATED PRECISION (float32
    equations whose matrix products round their operands to the
    configuration's compute dtype and sum in float32: the reference's
    ``operands``) on one batch of the mix at the timed sizes; logs every
    compared number beside its limit and returns the verdict. Limits:
    ``cfg["correct"]["reference"]``.

    ``stand_in`` is for taking the limits' second readings (PERF.md): the
    reference's own equations take the system's place, changed as the dict
    says. ``{"dtype": "bfloat16"}`` computes them in that dtype throughout
    (operands, sums, results, router, softmax, norms: the nearest precision
    below the stated one); any other key replaces that key of the
    reference's configuration (a planted fault, ``{"sliding_window": 1023}``).
    Each has to come out as not correct."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models import decoder_lm

    ref = _reference()
    limits = cfg["correct"]["reference"]
    batch = int(cfg["deployment"]["batch_per_chip"])
    tokens = jnp.asarray(generator.draw(mix, cfg, seed + 1, batch))
    x, y = tokens[:, :-1], tokens[:, 1:]
    at = jnp.asarray(np.random.default_rng(seed).integers(
        0, x.shape[1], size=(batch, 256 // batch)))
    marks, t0 = {}, time.perf_counter()

    def mark(name):
        marks[name] = round(time.perf_counter() - t0, 2)

    stated = cfg["dtypes"]["compute"]
    rcfg = decoder_lm.reference_config(model_config(cfg))
    rcfg["operands"] = None if stated == "float32" else stated
    if stand_in is None:
        params, loss, grads, picked, counters = system_loss_and_grad(
            cfg, x, y, at, seed)
        rparams = decoder_lm.reference_params(params)
        grads = decoder_lm.reference_params(grads)
    else:
        rparams = decoder_lm.reference_params(seeded_parameters(cfg, x, seed))
        changed = {k: v for k, v in stand_in.items() if k != "dtype"}
        low = stand_in.get("dtype")
        with jax.default_matmul_precision("highest"):
            loss, grads, counts, picked = ref.loss_and_grad(
                rparams if low is None else jax.tree_util.tree_map(
                    lambda a: a.astype(low), rparams),
                x, y, {**rcfg, **changed,
                       "operands": None if low else rcfg["operands"]},
                block_q, at)
        loss, counters = float(loss), ref.routing_counters(counts)
    mark("system")
    # the system's gradients wait on the host while the reference runs
    grads = jax.device_get(jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), grads))
    picked = np.asarray(picked, np.float32)
    mark("system_on_host")
    with jax.default_matmul_precision("highest"):
        rloss, rgrads, counts, rpicked = ref.loss_and_grad(
            rparams, x, y, rcfg, block_q, at)
    rcounters = ref.routing_counters(counts)
    rgrads, rpicked = jax.device_get(rgrads), np.asarray(rpicked)
    mark("reference_on_host")

    def rel(a, b):
        return float(np.linalg.norm((a - b).ravel())
                     / max(np.linalg.norm(b.ravel()), 1e-30))

    flat = jax.tree_util.tree_leaves_with_path(grads)
    rflat = jax.tree_util.tree_leaves(rgrads)
    grad_err = {jax.tree_util.keystr(p): rel(np.asarray(a), np.asarray(b))
                for (p, a), b in zip(flat, rflat)}
    worst = max(grad_err, key=grad_err.get)
    got = {
        "loss_abs": abs(loss - float(rloss)),
        "logits_abs": float(np.max(np.abs(picked - rpicked))),
        # the two tensors with the fewest kernels between them and what is
        # compared: they tell the precisions apart (PERF.md section 6)
        "grad_rel_l2_head": grad_err["['head']"],
        "grad_rel_l2_first_router": grad_err["['layers'][0]['router']"],
        # the worst tensor is a later layer's router or expert matrix, where
        # a token whose 8th and 9th expert are close swaps them under any
        # noise: it tells wrong mathematics from right
        "grad_rel_l2_worst": grad_err[worst],
        "pairs_local_rel": abs(counters["moe_pairs_local"]
                               - rcounters["moe_pairs_local"])
        / max(rcounters["moe_pairs_local"], 1.0),
        "load_max_over_mean_abs": abs(counters["moe_load_max_over_mean"]
                                      - rcounters["moe_load_max_over_mean"]),
        "dropped_pairs": counters["moe_dropped_pairs"],
    }
    mark("compared")
    broken = [k for k, v in got.items() if not v <= limits[k]]
    log(reference_comparison={k: {"value": v, "limit": limits[k]}
                              for k, v in got.items()},
        stand_in=stand_in, reference_operands=rcfg["operands"],
        loss=loss, reference_loss=float(rloss), worst_gradient=worst,
        counters=counters, reference_counters=rcounters,
        gradient_rel_l2=grad_err, seconds_until=marks, broken=broken)
    return not broken
