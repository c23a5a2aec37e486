"""Olmo-Hybrid-7B, one 4-layer period (3 Gated DeltaNet layers and 1 full
attention layer without rotary, under Olmo's post-norm blocks) as one
pipeline stage on one chip, through ``LocalOptimizer.optimize()``: the model
is ``bigdl_tpu.models.decoder_lm.from_config`` of the configuration's JSON,
whose keys are the model's public ``config.json`` keys.

Beside ``build``:

* the forward pass **in counting form** (``forward``): a function of
  ``dot_general``s only, whose shapes are exactly the forward work the
  equations need, the chunked delta rule's products included
  (``lib/flops.py`` walks ``dot_general``);
* the operations and least bytes of the kernels (``attention_cost``: zero for
  a linear-attention layer, so ``attn_roofline.train`` reads the one
  attention layer; ``delta_cost``: the gated delta rule, whatever implements
  it), which the roofline readers take from ``run.forward``;
* ``compare``: the comparison with the float32 reference
  (``olmo_hybrid_7b_reference.py``, the benchmark's own copy: the recurrence
  token by token, not a chunked form) that driver ``train_ref`` ANDs into
  ``correct``.
"""

from __future__ import annotations

from types import SimpleNamespace

BF16, F32 = 2, 4
# the reference's planted faults, for the limits' second readings (with
# {"dtype": "bfloat16"}, the control): each key a change of its configuration
FAULTS = ({"linear_allow_neg_eigval": False}, {"qk_l2norm": False},
          {"decay": False}, {"gate_then_norm": True}, {"post_norm": False},
          {"qk_norm_per_head": True})
CHUNK = 64   # ops/delta_rule.delta_rule's default chunk, counted below


def _layer_kinds(cfg: dict):
    return list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]


def _head_dim(cfg: dict) -> int:
    return int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])


def visible_pairs(t: int) -> int:
    """(query, key) pairs a causal layer sees over one sequence and head."""
    return t * (t + 1) // 2


def chunk_pairs(t: int, chunk: int) -> int:
    """(token, earlier token or itself) pairs inside the chunks of one
    record."""
    whole, rest = divmod(t, chunk)
    return whole * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2


def _delta_sizes(cfg: dict):
    return (int(cfg["deployment"]["record_tokens"]),
            int(cfg["linear_num_key_heads"]), int(cfg["linear_key_head_dim"]),
            int(cfg["linear_value_head_dim"]))


def delta_products(cfg: dict, records: int, layer: str = "delta"):
    """(name, m, k, n) of the chunked delta rule's products in one layer over
    ``records`` records: K K^T below the diagonal and Q K^T on and below it
    inside each chunk, the triangular solve for U and W (forward
    substitution: one multiply-add a pair below the diagonal and column of
    [V, K]), and over all tokens W S^T, Q S^T and the state's update
    Delta^T K, and (Q K^T * Gamma) Delta over the chunk's pairs."""
    t, h, dk, dv = _delta_sizes(cfg)
    pairs = records * h * chunk_pairs(t, CHUNK)
    below = pairs - records * h * t
    rows = records * h * t
    return [(f"{layer}.kk", below, dk, 1), (f"{layer}.qk", pairs, dk, 1),
            (f"{layer}.solve", below, 1, dk + dv),
            (f"{layer}.ws", rows, dk, dv), (f"{layer}.qs", rows, dk, dv),
            (f"{layer}.state", rows, dv, dk),
            (f"{layer}.intra", pairs, 1, dv)]


def products(cfg: dict, records: int):
    """(name, m, k, n) of every matrix product of one forward pass over
    ``records`` records: the work the equations need, no masked tile, no
    recomputation."""
    t, h, dk, dv = _delta_sizes(cfg)
    rows = records * t
    d_model, d = int(cfg["hidden_size"]), _head_dim(cfg)
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    f = int(cfg["intermediate_size"])
    out = []
    for i, kind in enumerate(_layer_kinds(cfg)):
        if kind == "linear_attention":
            out += [(f"l{i}.wq", rows, d_model, h * dk),
                    (f"l{i}.wk", rows, d_model, h * dk),
                    (f"l{i}.wv", rows, d_model, h * dv),
                    (f"l{i}.wz", rows, d_model, h * dv),
                    (f"l{i}.wa", rows, d_model, h),
                    (f"l{i}.wb", rows, d_model, h)]
            out += delta_products(cfg, records, f"l{i}.delta")
            out.append((f"l{i}.wo", rows, h * dv, d_model))
        else:
            seen = records * hq * visible_pairs(t)
            out += [(f"l{i}.wq", rows, d_model, hq * d),
                    (f"l{i}.wk", rows, d_model, hkv * d),
                    (f"l{i}.wv", rows, d_model, hkv * d),
                    (f"l{i}.qk", seen, d, 1),       # 2 d FLOPs a visible pair
                    (f"l{i}.pv", d, seen, 1),       # and 2 d more
                    (f"l{i}.wo", rows, hq * d, d_model)]
        out += [(f"l{i}.mlp_in", rows, d_model, 2 * f),
                (f"l{i}.mlp_out", rows, f, d_model)]
    out.append(("head", rows, d_model, int(cfg["vocab_size"])))
    return out


def attention_cost(cfg: dict, records: int, kind: str):
    """(FLOPs, least bytes) of one layer's Q.K^T and P.V over the visible
    pairs: q, k, v and the output once each (30 heads of 128), all in the
    compute dtype; nothing for a linear-attention layer."""
    if kind == "linear_attention":
        return 0.0, 0.0
    t, d = int(cfg["deployment"]["record_tokens"]), _head_dim(cfg)
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    flops = 4.0 * d * records * hq * visible_pairs(t)
    return flops, float(records * t * d * 2 * (hq + hkv) * BF16)


def delta_cost(cfg: dict, records: int):
    """(FLOPs, least bytes) of one layer's gated delta rule over ``records``
    records, whatever computes it: the chunked form's products
    (``delta_products``); q and k in the compute dtype, v in the compute
    dtype, log alpha and beta in float32, o out in float32, each once."""
    t, h, dk, dv = _delta_sizes(cfg)
    flops = sum(2.0 * m * k * n for _, m, k, n in delta_products(cfg, records))
    nbytes = records * t * h * (2 * dk * BF16 + dv * BF16 + 2 * F32 + dv * F32)
    return flops, float(nbytes)


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

    forward.attention_cost = lambda kind: attention_cost(cfg, traffic.batch, kind)
    forward.delta_cost = lambda: delta_cost(cfg, traffic.batch)
    forward.layer_kinds = _layer_kinds(cfg)
    return {"optimizer": opt, "forward": forward}


# --------------------------------------------------------------------------
# the comparison with the reference
# --------------------------------------------------------------------------

def _reference():
    """The benchmark's own copy of the reference, loaded as the harness
    loads every file: by name, from this directory's root."""
    from benchmark import run as bench

    return bench.load_module("configs", "olmo_hybrid_7b_reference",
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


def system_step(cfg: dict, x, y, seed: int):
    """Seeded weights before and after ONE step of the optimizer's own
    compiled train step (``optimize()`` for one iteration) on the batch, as
    host copies; the device holds nothing of the optimizer afterwards."""
    import gc

    import jax

    from bigdl_tpu.optim.trigger import Trigger

    opt = _seeded_optimizer(cfg, x, y, seed)
    before = jax.device_get(opt.model.get_parameters())
    opt.set_end_when(Trigger.max_iteration(1))
    opt.optimize()
    after = jax.device_get(opt.model.get_parameters())
    # the optimizer's reference cycles keep its 11 GB of state on the
    # device until collected
    del opt
    gc.collect()
    return before, after


def adam_first_step(cfg: dict, params, grads):
    """The configuration's optimizer at its first iteration, plainly: Adam
    at the warm-up's first rate, its moments bias-corrected from one
    gradient, applied in float32 to ``params`` (host arrays) in the order
    ``optim.Adam`` applies it."""
    import jax
    import numpy as np

    o, f32 = cfg["optimizer"], np.float32
    warmup = int(o["warmup_steps"])
    lr = f32(o["learning_rate"] / warmup if warmup else o["learning_rate"])
    b1, b2, eps = f32(o["beta1"]), f32(o["beta2"]), f32(o["epsilon"])

    def one(p, g):
        g = np.asarray(g, f32)
        m, v = (f32(1) - b1) * g, (f32(1) - b2) * g * g
        return np.asarray(p, f32) - lr * (m / (f32(1) - b1)) / (
            np.sqrt(v / (f32(1) - b2)) + eps)

    return jax.tree_util.tree_map(one, params, grads)


def update_errors(before, after, want_after):
    """The parameters' change ``after - before`` against ``want_after -
    before`` (host trees of one layout): the relative L2 error over the
    whole tree; each leaf's (a leaf that neither side changes reads 0); and
    the share of the changed numbers whose change has the other sign."""
    import jax
    import numpy as np

    off = want = flipped = changed = 0.0
    leaf = {}
    for (path, p), got, ref in zip(
            jax.tree_util.tree_leaves_with_path(before),
            jax.tree_util.tree_leaves(after),
            jax.tree_util.tree_leaves(want_after)):
        got = np.asarray(got, np.float32) - p
        ref = np.asarray(ref, np.float32) - p
        o, w = float(np.sum((got - ref) ** 2)), float(np.sum(ref * ref))
        off, want = off + o, want + w
        leaf[jax.tree_util.keystr(path)] = (o / max(w, 1e-60)) ** 0.5 \
            if o else 0.0
        flipped += float(np.sum(got * ref < 0))
        changed += float(np.count_nonzero(ref))
    return (off / max(want, 1e-60)) ** 0.5, leaf, flipped / max(changed, 1.0)


def compare(cfg: dict, mix: dict, generator, seed: int, log,
            block_q: int = 512, stand_in: dict = None) -> bool:
    """The system against the reference AT THE STATED PRECISION (float32
    equations whose matrix products round their operands to the
    configuration's compute dtype and sum in float32: the reference's
    ``operands``; its recurrence float32, token by token) on one batch of the
    mix at the timed sizes; logs every compared number beside its limit and
    returns the verdict. Limits: ``cfg["correct"]["reference"]``.

    Beside the loss, logits, gradients and counter of one pass, the
    parameters after ONE step of the optimizer's own compiled train step
    (``system_step``) against plain Adam applied to the reference's
    gradient (``adam_first_step``): the relative L2 error of the change over
    the whole model. A step that leaves the parameters unchanged reads 1.

    ``stand_in`` is for taking the limits' second readings (PERF.md): the
    reference's own equations take the system's place, changed as the dict
    says. ``{"dtype": "bfloat16"}`` computes them in that dtype throughout
    (operands, sums, results, recurrence, softmax, norms: the nearest
    precision below the stated one); any other key replaces that key of the
    reference's configuration (a planted fault, ``FAULTS``: beta not
    doubled, q and k not L2-normed, alpha = 1, the gate before the norm,
    pre-norm blocks, a per-head q/k norm). Each has to come out as not
    correct."""
    import gc
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
        # the step first, on a device that holds nothing else
        before, after = system_step(cfg, x, y, seed)
        mark("system_step")
        loss, grads, picked, counters = system_loss_and_grad(
            cfg, x, y, at, seed)[1:]
        # the system's weights on the device, which the reference's host
        # copy would sit beside
        gc.collect()
        rparams = decoder_lm.reference_params(before)
        after = decoder_lm.reference_params(after)
        grads = decoder_lm.reference_params(grads)
    else:
        rparams = decoder_lm.reference_params(jax.device_get(
            seeded_parameters(cfg, x, seed)))
        changed = {k: v for k, v in stand_in.items() if k != "dtype"}
        low = stand_in.get("dtype")
        with jax.default_matmul_precision("highest"):
            loss, grads, beta, picked = ref.loss_and_grad(
                rparams if low is None else jax.tree_util.tree_map(
                    lambda a: jnp.asarray(a).astype(low), rparams),
                x, y, {**rcfg, **changed,
                       "operands": None if low else rcfg["operands"]},
                block_q, at)
        loss, counters = float(loss), ref.delta_counters(beta)
    mark("system")
    # the system's gradients wait on the host while the reference runs
    grads = jax.device_get(jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), grads))
    picked = np.asarray(picked, np.float32)
    mark("system_on_host")
    with jax.default_matmul_precision("highest"):
        rloss, rgrads, rbeta, rpicked = ref.loss_and_grad(
            rparams, x, y, rcfg, block_q, at)
    rcounters = ref.delta_counters(rbeta)
    rgrads, rpicked = jax.device_get(rgrads), np.asarray(rpicked)
    mark("reference_on_host")
    if stand_in is not None:
        # the stand-in's optimizer is plain Adam too
        after = adam_first_step(cfg, rparams, grads)
    update, update_leaf, flipped = update_errors(
        rparams, after, adam_first_step(cfg, rparams, rgrads))
    update_worst = max(update_leaf, key=update_leaf.get)

    def rel(a, b):
        return float(np.linalg.norm((a - b).ravel())
                     / max(np.linalg.norm(b.ravel()), 1e-30))

    flat = jax.tree_util.tree_leaves_with_path(grads)
    rflat = jax.tree_util.tree_leaves(rgrads)
    grad_err = {jax.tree_util.keystr(p): rel(np.asarray(a), np.asarray(b))
                for (p, a), b in zip(flat, rflat)}
    worst = max(grad_err, key=grad_err.get)
    kinds = rcfg["layer_types"]
    first = kinds.index("linear_attention")
    full = kinds.index("full_attention")

    def leaf(layer, name):
        return grad_err[f"['layers'][{layer}]['{name}']"]

    got = {
        "loss_abs": abs(loss - float(rloss)),
        "logits_abs": float(np.max(np.abs(picked - rpicked))),
        # the tensor with the fewest kernels between it and what is compared
        "grad_rel_l2_head": grad_err["['head']"],
        # the first linear layer's write strength, decay, keys and output
        # norm: the leaves whose gradients cross the whole delta rule and
        # every layer above it
        "grad_rel_l2_first_wb": leaf(first, "wb"),
        "grad_rel_l2_first_A_log": leaf(first, "A_log"),
        "grad_rel_l2_first_wk": leaf(first, "wk"),
        "grad_rel_l2_first_norm": leaf(first, "norm"),
        # the full layer's query projection and its whole-width norm
        "grad_rel_l2_full_wq": leaf(full, "wq"),
        "grad_rel_l2_full_q_norm": leaf(full, "q_norm"),
        "grad_rel_l2_worst": grad_err[worst],
        "beta_mean_abs": abs(counters["delta_beta_mean"]
                             - rcounters["delta_beta_mean"]),
        # one step of the train step against plain Adam on the reference's
        # gradient, over the whole model: at the first step Adam moves each
        # number by the rate times the sign of its gradient, so a leaf's own
        # reading is 2 sqrt(the share of its signs flipped) and counts the
        # few flips of a small leaf (logged, not compared)
        "update_rel_l2": update,
    }
    mark("compared")
    broken = [k for k, v in got.items() if not v <= limits[k]]
    log(reference_comparison={k: {"value": v, "limit": limits[k]}
                              for k, v in got.items()},
        stand_in=stand_in, reference_operands=rcfg["operands"],
        loss=loss, reference_loss=float(rloss), worst_gradient=worst,
        counters=counters, reference_counters=rcounters,
        update_flipped_share=flipped, worst_update_leaf=update_worst,
        update_rel_l2_by_leaf=update_leaf,
        gradient_rel_l2=grad_err, seconds_until=marks, broken=broken)
    return not broken
