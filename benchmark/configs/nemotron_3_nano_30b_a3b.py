"""NVIDIA-Nemotron-3-Nano-30B-A3B, one chip's share of a 16-way
expert-parallel deployment (layers 34 - 42 of the published pattern,
``EMEMEMEM*``: four routed-expert layers, four Mamba-2 layers of 8 B/C groups
and one attention layer, each ONE mixer behind one norm), through
``LocalOptimizer.optimize()``: the model is
``bigdl_tpu.models.decoder_lm.from_config`` of the configuration's JSON, whose
keys are the model's public ``config.json`` keys.

Beside ``build``:

* the forward pass **in counting form** (``forward``): a function of
  ``dot_general``s only, whose shapes are exactly the forward work the
  equations need, the scan's four products and the routed experts' two
  included (``lib/flops.py`` walks ``dot_general``, and would count one tile
  of a Pallas kernel and no grouped product at all);
* the operations and least bytes of the kernels (``attention_cost``: 32 query
  over 2 K/V heads of 128, zero for the other layer kinds; ``ssd_cost``: the
  state-space scan with 8 B/C groups, whatever implements it;
  ``experts_cost``: the TWO grouped products of the routed layers, the shared
  expert apart), which the roofline readers take from ``run.forward``;
* ``compare``: the comparison with the float32 reference
  (``nemotron_3_nano_30b_a3b_reference.py``, the benchmark's own copy: the
  sequential recurrence, a masked loop over the experts held) that driver
  ``train_ref`` ANDs into ``correct``.
"""

from __future__ import annotations

from types import SimpleNamespace

BF16, F32 = 2, 4
# stand-in for the routers' biases in the comparison: what a few hundred
# steps of one sign at rate 0.001 leave behind, so that choice by s + b
# differs from choice by s (at the start b is zero and the two are one) and a
# bias that leaked into the weights would show (joyai_llm_flash's finding: at
# 0.05 it did not, a sixteenth of the routed pairs being local)
COMPARED_BIAS = 0.25
# the reference's planted faults, for the limits' second readings
FAULTS = ("scan_group_zero", "norm_over_all", "gated_expert", "bias_in_weights")


def layer_kinds(cfg: dict):
    """The kind of every layer the step runs ("mamba" | "attention" |
    "experts"): ``num_hidden_layers`` letters of the published pattern from
    ``first_layer`` on, as the program reads them."""
    from bigdl_tpu.models import decoder_lm

    return decoder_lm.layer_types(cfg)


def visible_pairs(t: int) -> int:
    """(query, key) pairs a causal layer sees over one sequence and head."""
    return t * (t + 1) // 2


def chunk_pairs(t: int, chunk: int) -> int:
    """(token, earlier token or itself) pairs inside the chunks of one
    record: what the scan's two in-chunk products run over."""
    whole, rest = divmod(t, chunk)
    return whole * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2


def _scan_sizes(cfg: dict):
    return (int(cfg["deployment"]["record_tokens"]), int(cfg["chunk_size"]),
            int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"]),
            int(cfg["ssm_state_size"]), int(cfg["n_groups"]))


def expected_pairs(cfg: dict, records: int) -> int:
    """(token, choice) pairs an even router sends to the experts held, a
    routed layer and step."""
    rows = records * int(cfg["deployment"]["record_tokens"])
    return rows * int(cfg["num_experts_per_tok"]) * len(cfg["experts_held"]) \
        // int(cfg["router_width"])


def products(cfg: dict, records: int):
    """(name, m, k, n) of every matrix product of one forward pass over
    ``records`` records: the work the equations need, no masked tile, no
    recomputation, routed experts over the expected pairs of the experts
    held, the shared expert over every token."""
    t, q, h, p, s, g = _scan_sizes(cfg)
    rows = records * t
    d_model, d = int(cfg["hidden_size"]), int(cfg["head_dim"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    fe = int(cfg["moe_intermediate_size"])
    fs = int(cfg["moe_shared_expert_intermediate_size"]) \
        * int(cfg["n_shared_experts"])
    in_chunk = records * chunk_pairs(t, q)
    local = expected_pairs(cfg, records)
    out = []
    for i, kind in enumerate(layer_kinds(cfg)):
        if kind == "mamba":
            out += [(f"l{i}.in_proj", rows, d_model, 2 * h * p + 2 * g * s + h),
                    (f"l{i}.scan_cb", in_chunk, g * s, 1),  # C.B^T, a group's
                    (f"l{i}.scan_lx", in_chunk, 1, h * p),  # (C.B^T * L).X
                    (f"l{i}.scan_states", rows, s, h * p),  # B^T.(decay * X)
                    (f"l{i}.scan_cs", rows, s, h * p),      # C.S
                    (f"l{i}.out_proj", rows, h * p, d_model)]
        elif kind == "attention":
            seen = records * hq * visible_pairs(t)
            out += [(f"l{i}.wq", rows, d_model, hq * d),
                    (f"l{i}.wk", rows, d_model, hkv * d),
                    (f"l{i}.wv", rows, d_model, hkv * d),
                    (f"l{i}.qk", seen, d, 1),       # 2 d FLOPs a visible pair
                    (f"l{i}.pv", d, seen, 1),       # and 2 d more
                    (f"l{i}.wo", rows, hq * d, d_model)]
        else:
            out += [(f"l{i}.router", rows, d_model, int(cfg["router_width"])),
                    (f"l{i}.w_up", local, d_model, fe),
                    (f"l{i}.w_down", local, fe, d_model),
                    (f"l{i}.shared_in", rows, d_model, fs),
                    (f"l{i}.shared_out", rows, fs, d_model)]
    out.append(("head", rows, d_model, int(cfg["vocab_size"])))
    return out


def attention_cost(cfg: dict, records: int, kind: str):
    """(FLOPs, least bytes) of one layer's Q.K^T and P.V over the visible
    pairs: q and the output once each (32 heads), k and v once (2 heads), all
    in the compute dtype; nothing for a mamba or an experts layer."""
    if kind != "attention":
        return 0.0, 0.0
    t, d = int(cfg["deployment"]["record_tokens"]), int(cfg["head_dim"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    flops = 4.0 * d * records * hq * visible_pairs(t)
    return flops, float(records * t * d * 2 * (hq + hkv) * BF16)


def ssd_cost(cfg: dict, records: int):
    """(FLOPs, least bytes) of one layer's state-space scan over ``records``
    records, whatever computes it: C.B^T (each of the 8 groups' own) and
    (C.B^T * L).X over the pairs inside a chunk, the chunks' states
    B^T.(decay * X) and C.S over all tokens; x in the compute dtype, y out in
    float32, B and C (8 groups of 128) in the compute dtype, the step size in
    float32, each once."""
    t, q, h, p, s, g = _scan_sizes(cfg)
    pairs = chunk_pairs(t, q)
    flops = records * (2.0 * s * g * pairs + 2.0 * h * p * pairs
                       + 2 * (2.0 * t * h * p * s))
    nbytes = records * t * (h * p * BF16 + h * p * F32 + 2 * g * s * BF16
                            + h * F32)
    return flops, float(nbytes)


def experts_cost(cfg: dict, pairs: float, layers: int):
    """(FLOPs, least bytes) of the TWO grouped products (``W_up``, then
    ``W_down`` of ``relu^2``) over ``pairs`` routed pairs in all (``layers``
    routed layers together): each held expert's two matrices once a layer,
    the pairs' rows in and out once each (operands in the compute dtype,
    results float32). The shared expert is plain products in scope
    ``moe_shared`` and not counted here."""
    d_model, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    held = len(cfg["experts_held"])
    flops = pairs * 2 * 2.0 * d_model * f
    weights = layers * held * 2 * d_model * f * BF16
    rows = pairs * (d_model * BF16 + f * F32 + f * BF16 + d_model * F32)
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
    model = decoder_lm.from_config(cfg)
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

    kinds = layer_kinds(cfg)
    forward.attention_cost = lambda kind: attention_cost(cfg, traffic.batch, kind)
    forward.ssd_cost = lambda: ssd_cost(cfg, traffic.batch)
    forward.experts_cost = lambda pairs: experts_cost(
        cfg, pairs, kinds.count("experts"))
    forward.layer_kinds = kinds
    return {"optimizer": opt, "forward": forward}


# --------------------------------------------------------------------------
# the comparison with the reference
# --------------------------------------------------------------------------

def _reference():
    """The benchmark's own copy of the reference, loaded as the harness
    loads every file: by name, from this directory's root."""
    from benchmark import run as bench

    return bench.load_module("configs", "nemotron_3_nano_30b_a3b_reference",
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
    state, counters and logits at the positions ``at`` (N, m) on one batch,
    from the function the train step differentiates: the optimizer's own
    ``_loss_fn`` over the same module, criterion, dtype policy and kernels,
    jitted at these shapes. The logits leave through a forward hook on the
    head (the state pytree is the step's side channel), so it is one pass and
    one compile."""
    import jax
    import jax.numpy as jnp

    opt = _seeded_optimizer(cfg, x, y, seed)
    model = opt.model
    rows = jnp.arange(x.shape[0])[:, None]
    head = model.modules[-1]
    head.register_forward_hook(
        lambda module, inp, out: {"_picked": out[rows, at]})
    params, state = model.get_parameters(), seeded_state(model, seed)
    (loss, new_state), grads = jax.jit(jax.value_and_grad(
        opt._loss_fn, has_aux=True))(params, state, x, y, jax.random.PRNGKey(0))
    counters = {k: float(v) for k, v in model.counters_tree(new_state).items()}
    return (params, state, float(loss), grads, new_state,
            new_state[head.name()]["_picked"], counters)


def compare(cfg: dict, mix: dict, generator, seed: int, log,
            block_q: int = 512, stand_in: dict = None) -> bool:
    """The system against the reference AT THE STATED PRECISION (float32
    equations whose matrix products round their operands to the
    configuration's compute dtype and sum in float32: the reference's
    ``operands``; its recurrence, router, softmax and norms float32) on one
    batch of the mix at the timed sizes, with seeded weights and seeded
    non-zero router biases; logs every compared number beside its limit and
    returns the verdict. Limits: ``cfg["correct"]["reference"]``.

    ``stand_in`` is for taking the limits' second readings (PERF.md): the
    reference's own equations take the system's place, changed as the dict
    says. ``{"dtype": "bfloat16"}`` computes them in that dtype throughout
    (the nearest precision below the stated one); any other key sets that key
    of the reference's configuration, a planted fault (``FAULTS``):
    ``{"scan_group_zero": True}`` (B and C of group 0 handed to every head),
    ``{"norm_over_all": True}`` (the gated norm's statistic over all of
    d_inner), ``{"gated_expert": True}``, ``{"bias_in_weights": True}``. Each
    has to come out as not correct."""
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
    rcfg = decoder_lm.reference_config(cfg)
    rcfg["operands"] = None if stated == "float32" else stated
    if stand_in is None:
        params, state, loss, grads, new_state, picked, counters = \
            system_loss_and_grad(cfg, x, y, at, seed)
        rparams = decoder_lm.reference_params(params)
        rbiases = decoder_lm.reference_biases(state)
        grads = decoder_lm.reference_params(grads)
        biases = decoder_lm.reference_biases(new_state)
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
        counters = ref.counters(stats, rcfg, *x.shape)
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
    rcounters = ref.counters(rstats, rcfg, *x.shape)
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
    kinds = rcfg["layer_types"]
    mamba, routed = kinds.index("mamba"), kinds.index("experts")
    # W_in's B and C columns: behind z and x, before dt
    d_inner = int(cfg["mamba_num_heads"]) * int(cfg["mamba_head_dim"])
    bc = slice(2 * d_inner, 2 * d_inner + 2 * int(cfg["n_groups"])
               * int(cfg["ssm_state_size"]))

    def leaf_rel(layer, name, pick=lambda a: a):
        return rel(pick(np.asarray(grads["layers"][layer][name])),
                   pick(np.asarray(rgrads["layers"][layer][name])))

    def counter_rel(name):
        return abs(counters[name] - rcounters[name]) / max(
            abs(rcounters[name]), 1e-30)

    # the bias after the step: exact, but for experts whose count sits so near
    # the mean that one swapped pair turns the sign
    counts = np.asarray(rstats["counts"], np.float64)
    clear = np.abs(counts - counts.mean(axis=-1, keepdims=True)) \
        > float(limits["bias_count_slack"])
    differs = np.stack([np.abs(b - np.asarray(rb)) > 1e-7
                        for b, rb in zip(biases, rstats["biases"])])
    got = {
        "loss_abs": abs(loss - float(rloss)),
        "logits_abs": float(np.max(np.abs(picked - rpicked))),
        "grad_rel_l2_head": grad_err["['head']"],
        # the first state-space layer's decay, step size, conv and the B/C
        # columns of its input projection: the leaves whose gradients cross
        # the whole grouped scan and every layer above it
        "grad_rel_l2_first_A_log": leaf_rel(mamba, "A_log"),
        "grad_rel_l2_first_dt_bias": leaf_rel(mamba, "dt_bias"),
        "grad_rel_l2_first_conv": leaf_rel(mamba, "conv_w"),
        "grad_rel_l2_first_in_proj_bc": leaf_rel(mamba, "in_proj",
                                                 lambda a: a[:, bc]),
        "grad_rel_l2_first_router": leaf_rel(routed, "router"),
        "grad_rel_l2_first_shared_in": leaf_rel(routed, "shared_in"),
        # linear in the routing weights, and routed pairs are a sixteenth of
        # the layer here: what tells a fault in the weights from rounding
        "grad_rel_l2_first_w_down": leaf_rel(routed, "w_down"),
        "grad_rel_l2_worst": grad_err[worst],
        "log_decay_min_rel": counter_rel("ssm_log_decay_min"),
        "state_rms_rel": counter_rel("ssm_state_rms"),
        "pairs_local_rel": counter_rel("moe_pairs_local"),
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
