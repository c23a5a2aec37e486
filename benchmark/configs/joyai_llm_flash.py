"""JoyAI-LLM-Flash, one chip's share of a 16-way expert-parallel deployment
(the leading dense layer, four sparse layers and the multi-token-prediction
module), through ``LocalOptimizer.optimize()``: the model is
``bigdl_tpu.models.decoder_lm.from_config`` of the configuration's JSON, whose
keys are the model's public ``config.json`` keys.

Beside ``build``:

* the forward pass **in counting form** (``forward``): a function of
  ``dot_general``s only, whose shapes are exactly the forward work the
  equations need (``lib/flops.py`` walks ``dot_general``, and would count one
  tile of a Pallas kernel and no grouped product at all);
* the operations and least bytes of the two kernels (``attention_cost``: q and
  k heads of 192, v and output heads of 128; ``experts_cost``: the grouped
  products of the five routed layers, the shared expert apart), which the
  roofline readers take from ``run.forward``;
* ``compare``: the comparison with the float32 reference
  (``joyai_llm_flash_reference.py``, the benchmark's own copy) that driver
  ``train_ref`` ANDs into ``correct``.
"""

from __future__ import annotations

from types import SimpleNamespace

BF16, F32 = 2, 4
# stand-in for the routers' biases in the comparison: what a few hundred
# steps of one sign at rate 0.001 leave behind, so that choice by s + b
# differs from choice by s (at the start b is zero and the two are one) and
# a bias that leaked into the weights would show (at 0.05 it did not: PERF.md)
COMPARED_BIAS = 0.25


def model_config(cfg: dict) -> dict:
    """The builder's dict: the JSON's keys, with ``n_routed_experts`` back at
    the router's width (the file counts the experts HELD under that key, as
    the cut asks; ``experts_held`` names them)."""
    return {**cfg, "n_routed_experts": int(cfg["router_width"])}


def _layers(cfg: dict):
    """("dense" | "sparse") of every block the step runs: the main layers,
    then the MTP module's."""
    n, dense = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    return ["dense" if i < dense else "sparse" for i in range(n)] + [
        "sparse"] * int(cfg["num_nextn_predict_layers"])


def visible_pairs(t: int) -> int:
    """(query, key) pairs a causal layer sees over one sequence and head."""
    return t * (t + 1) // 2


def products(cfg: dict, records: int):
    """(name, m, k, n) of every matrix product of one forward pass over
    ``records`` records: the work the equations need, no masked tile, no
    recomputation, routed experts over the expected pairs of the experts
    held, the shared expert over every token."""
    t = int(cfg["deployment"]["record_tokens"])
    rows = records * t
    d_model, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    rq, rkv = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    dn, dr, dv = (int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
                  int(cfg["v_head_dim"]))
    f, fe = int(cfg["intermediate_size"]), int(cfg["moe_intermediate_size"])
    fs = fe * int(cfg["n_shared_experts"])
    held, width = len(cfg["experts_held"]), int(cfg["router_width"])
    pairs = rows * int(cfg["num_experts_per_tok"]) * held // width
    seen = records * h * visible_pairs(t)
    vocab = int(cfg["vocab_size"])
    out = []
    for i, kind in enumerate(_layers(cfg)):
        out += [(f"l{i}.wq_a", rows, d_model, rq),
                (f"l{i}.wq_b", rows, rq, h * (dn + dr)),
                (f"l{i}.wkv_a", rows, d_model, rkv + dr),
                (f"l{i}.wkv_b", rows, rkv, h * (dn + dv)),
                (f"l{i}.qk", seen, dn + dr, 1),   # 2 (dn + dr) FLOPs a pair
                (f"l{i}.pv", dv, seen, 1),        # and 2 dv more
                (f"l{i}.wo", rows, h * dv, d_model)]
        if kind == "dense":
            out += [(f"l{i}.mlp_in", rows, d_model, 2 * f),
                    (f"l{i}.mlp_out", rows, f, d_model)]
        else:
            out += [(f"l{i}.router", rows, d_model, width),
                    (f"l{i}.w_gate", pairs, d_model, fe),
                    (f"l{i}.w_up", pairs, d_model, fe),
                    (f"l{i}.w_down", pairs, fe, d_model),
                    (f"l{i}.shared_in", rows, d_model, 2 * fs),
                    (f"l{i}.shared_out", rows, fs, d_model)]
    out.append(("head", rows, d_model, vocab))
    if int(cfg["num_nextn_predict_layers"]):
        out += [("mtp.eh_proj", rows, 2 * d_model, d_model),
                ("mtp.head", rows, d_model, vocab)]
    return out


def attention_cost(cfg: dict, records: int, kind: str):
    """(FLOPs, least bytes) of one layer's Q.K^T and P.V over the visible
    pairs: 2 * 192 + 2 * 128 FLOPs a pair; q and k once each at 192 a head, v
    and the output once each at 128, all in the compute dtype (k's rotary
    part is counted per head, as the kernel reads it)."""
    t = int(cfg["deployment"]["record_tokens"])
    h = int(cfg["num_attention_heads"])
    dqk = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    dv = int(cfg["v_head_dim"])
    flops = (2.0 * dqk + 2.0 * dv) * records * h * visible_pairs(t)
    return flops, float(records * t * h * (2 * dqk + 2 * dv) * BF16)


def experts_cost(cfg: dict, pairs: float, layers: int):
    """(FLOPs, least bytes) of the three grouped products over ``pairs``
    routed pairs in all (``layers`` routed layers together): each held
    expert's three matrices once a layer, the pairs' rows in and out once
    each (operands in the compute dtype, results float32). The shared expert
    is plain products in scope ``moe_shared`` and not counted here."""
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
    opt = LocalOptimizer(
        model, traffic.dataset,
        nn.MultiTokenCrossEntropyCriterion(float(cfg["mtp_loss_weight"])))
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

    layers = _layers(cfg)
    forward.attention_cost = lambda kind: attention_cost(cfg, traffic.batch, kind)
    forward.experts_cost = lambda pairs: experts_cost(
        cfg, pairs, layers.count("sparse"))
    # every block, the MTP module's among them, is one full causal layer
    forward.layer_kinds = ["full_attention"] * len(layers)
    return {"optimizer": opt, "forward": forward}


# --------------------------------------------------------------------------
# the comparison with the reference
# --------------------------------------------------------------------------

def _reference():
    """The benchmark's own copy of the reference, loaded as the harness
    loads every file: by name, from this directory's root."""
    from benchmark import run as bench

    return bench.load_module("configs", "joyai_llm_flash_reference",
                             (bench.HERE,))


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


def seeded_state(model, seed: int):
    """The model's state with every router's selection bias drawn from
    U(-COMPARED_BIAS, COMPARED_BIAS) by ``seed`` (see ``COMPARED_BIAS``)."""
    import jax

    def draw(path, leaf):
        name = jax.tree_util.keystr(path)
        if "selection_bias" not in name:
            return leaf
        key = jax.random.fold_in(jax.random.PRNGKey(seed % (2**31)),
                                 sum(map(ord, name)))
        return jax.random.uniform(key, leaf.shape, leaf.dtype,
                                  -COMPARED_BIAS, COMPARED_BIAS)

    return jax.tree_util.tree_map_with_path(draw, model.get_state())


def seeded_parameters(cfg: dict, x, seed: int):
    """The seeded weights and router biases the comparison runs on."""
    model = _seeded_optimizer(cfg, x, x, seed).model
    return model.get_parameters(), seeded_state(model, seed)


def system_loss_and_grad(cfg: dict, x, y, at, seed: int):
    """Seeded weights and biases, and the system's loss, gradients, new
    state, counters and both heads' logits at the positions ``at`` (N, m) on
    one batch, from the function the train step differentiates: the
    optimizer's own ``_loss_fn`` over the same module, criterion, dtype policy
    and kernels, jitted at these shapes. The logits leave through a forward
    hook on the model (the state pytree is the step's side channel), so it is
    one pass and one compile."""
    import jax
    import jax.numpy as jnp

    opt = _seeded_optimizer(cfg, x, y, seed)
    model = opt.model
    rows = jnp.arange(x.shape[0])[:, None]
    model.register_forward_hook(lambda module, inp, out: {"_picked": jnp.stack(
        [out[1][rows, at], out[2][rows, at]], axis=1)})
    params, state = model.get_parameters(), seeded_state(model, seed)
    (loss, new_state), grads = jax.jit(jax.value_and_grad(
        opt._loss_fn, has_aux=True))(params, state, x, y, jax.random.PRNGKey(0))
    counters = {k: float(v) for k, v in model.counters_tree(new_state).items()}
    return (params, state, float(loss), grads, new_state,
            new_state["_picked"], counters)


def compare(cfg: dict, mix: dict, generator, seed: int, log,
            block_q: int = 512, stand_in: dict = None) -> bool:
    """The system against the reference AT THE STATED PRECISION (float32
    equations whose matrix products round their operands to the
    configuration's compute dtype and sum in float32: the reference's
    ``operands``; router, softmax, norms float32) on one batch of the mix at
    the timed sizes, with seeded weights and seeded non-zero router biases;
    logs every compared number beside its limit and returns the verdict.
    Limits: ``cfg["correct"]["reference"]``.

    ``stand_in`` is for taking the limits' second readings (PERF.md): the
    reference's own equations take the system's place, changed as the dict
    says. ``{"dtype": "bfloat16"}`` computes them in that dtype throughout
    (the nearest precision below the stated one); any other key replaces that
    key of the reference's configuration, a planted fault:
    ``{"rope_interleave": False}``, ``{"softmax_scale": 128 ** -0.5}``,
    ``{"bias_in_weights": True}``, ``{"shared_expert": False}``,
    ``{"mtp_loss_weight": 0.0}``, ``{"experts_held": [1, ..., 16]}``. Each has
    to come out as not correct."""
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
        0, x.shape[1] - 1, size=(batch, 256 // batch)))
    marks, t0 = {}, time.perf_counter()

    def mark(name):
        marks[name] = round(time.perf_counter() - t0, 2)

    stated = cfg["dtypes"]["compute"]
    weight = float(cfg["mtp_loss_weight"])
    rcfg = decoder_lm.reference_config(model_config(cfg))
    rcfg["operands"] = None if stated == "float32" else stated
    rcfg["mtp_loss_weight"] = weight
    if stand_in is None:
        params, state, loss, grads, new_state, picked, counters = \
            system_loss_and_grad(cfg, x, y, at, seed)
        rparams = decoder_lm.reference_params(params)
        rbiases = decoder_lm.reference_biases(state)
        grads = decoder_lm.reference_params(grads)
        biases = decoder_lm.reference_biases(new_state)
        mtp_loss = counters["mtp_loss"]
    else:
        params, state = seeded_parameters(cfg, x, seed)
        rparams = decoder_lm.reference_params(params)
        rbiases = decoder_lm.reference_biases(state)
        changed = {k: v for k, v in stand_in.items() if k != "dtype"}
        low = stand_in.get("dtype")
        lowered = (lambda tree: tree) if low is None else (
            lambda tree: jax.tree_util.tree_map(lambda a: a.astype(low), tree))
        with jax.default_matmul_precision("highest"):
            loss, grads, stats, picked = ref.loss_and_grad(
                lowered(rparams), lowered(rbiases), x, y,
                {**rcfg, **changed,
                 "operands": None if low else rcfg["operands"]}, block_q, at)
        loss, biases = float(loss), stats["biases"]
        counters = ref.routing_counters(stats, {**rcfg, **changed})
        mtp_loss = counters["mtp_loss"]
    mark("system")
    # the system's gradients wait on the host while the reference runs
    grads = jax.device_get(jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), grads))
    picked = np.asarray(picked, np.float32)
    biases = [np.asarray(b, np.float32) for b in biases]
    mark("system_on_host")
    with jax.default_matmul_precision("highest"):
        rloss, rgrads, rstats, rpicked = ref.loss_and_grad(
            rparams, rbiases, x, y, rcfg, block_q, at)
    rcounters = ref.routing_counters(rstats, rcfg)
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
    first_sparse = _layers(cfg).index("sparse")
    # the bias after the step: exact, but for experts whose count sits so near
    # the mean that one swapped pair turns the sign
    counts = np.asarray(rstats["counts"], np.float64)
    clear = np.abs(counts - counts.mean(axis=-1, keepdims=True)) \
        > float(limits["bias_count_slack"])
    differs = np.stack([np.abs(b - np.asarray(rb)) > 1e-7
                        for b, rb in zip(biases, rstats["biases"])])
    got = {
        "loss_abs": abs(loss - float(rloss)),
        "main_loss_abs": abs(loss - weight * mtp_loss
                             - float(rstats["main_loss"])),
        "mtp_loss_abs": abs(mtp_loss - float(rstats["mtp_loss"])),
        "logits_abs": float(np.max(np.abs(picked[:, 0] - rpicked[:, 0]))),
        "mtp_logits_abs": float(np.max(np.abs(picked[:, 1] - rpicked[:, 1]))),
        # the tensors with the fewest kernels between them and what is
        # compared tell the precisions apart; the worst tells wrong
        # mathematics from right
        "grad_rel_l2_head": grad_err["['head']"],
        "grad_rel_l2_first_router":
            grad_err[f"['layers'][{first_sparse}]['router']"],
        "grad_rel_l2_first_wkv_b": grad_err["['layers'][0]['wkv_b']"],
        # linear in the routing weights, and routed pairs are a sixteenth of
        # the layer here: what tells a fault in the weights from rounding
        "grad_rel_l2_first_w_down":
            grad_err[f"['layers'][{first_sparse}]['w_down']"],
        "grad_rel_l2_worst": grad_err[worst],
        "pairs_local_rel": abs(counters["moe_pairs_local"]
                               - rcounters["moe_pairs_local"])
        / max(rcounters["moe_pairs_local"], 1.0),
        "load_max_over_mean_abs": abs(counters["moe_load_max_over_mean"]
                                      - rcounters["moe_load_max_over_mean"]),
        "dropped_pairs": counters["moe_dropped_pairs"],
        "bias_abs_max_abs": abs(counters["moe_bias_abs_max"]
                                - rcounters["moe_bias_abs_max"]),
        "bias_mismatched": int(np.sum(differs & clear)),
    }
    mark("compared")
    broken = [k for k, v in got.items() if not v <= limits[k]]
    log(reference_comparison={k: {"value": v, "limit": limits[k]}
                              for k, v in got.items()},
        stand_in=stand_in, reference_operands=rcfg["operands"],
        loss=loss, reference_loss=float(rloss), worst_gradient=worst,
        counters=counters, reference_counters=rcounters,
        bias_mismatched_any_count=int(np.sum(differs)),
        gradient_rel_l2=grad_err, seconds_until=marks, broken=broken)
    return not broken
