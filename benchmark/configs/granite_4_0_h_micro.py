"""granite-4.0-h-micro, one 10-layer period (9 Mamba-2 layers and 1 NoPE
attention layer, dense gated MLP, scaled residuals, tied head) as one
pipeline stage on one chip, through ``LocalOptimizer.optimize()``: the model
is ``bigdl_tpu.models.decoder_lm.from_config`` of the configuration's JSON,
whose keys are the model's public ``config.json`` keys.

Beside ``build``:

* the forward pass **in counting form** (``forward``): a function of
  ``dot_general``s only, whose shapes are exactly the forward work the
  equations need, the scan's four products included (``lib/flops.py`` walks
  ``dot_general``);
* the operations and least bytes of the kernels (``attention_cost``: zero for
  a mamba layer, so ``attn_roofline.train`` reads the one attention layer;
  ``ssd_cost``: the state-space scan, whatever implements it), which the
  roofline readers take from ``run.forward``;
* ``compare``: the comparison with the float32 reference
  (``granite_4_0_h_micro_reference.py``, the benchmark's own copy: the
  sequential recurrence, not a chunked form) that driver ``train_ref`` ANDs
  into ``correct``.
"""

from __future__ import annotations

from types import SimpleNamespace

BF16, F32 = 2, 4


def _layer_kinds(cfg: dict):
    return list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]


def _head_dim(cfg: dict) -> int:
    return int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])


def visible_pairs(t: int) -> int:
    """(query, key) pairs a causal layer sees over one sequence and head."""
    return t * (t + 1) // 2


def chunk_pairs(t: int, chunk: int) -> int:
    """(token, earlier token or itself) pairs inside the chunks of one
    record: what the scan's two in-chunk products run over."""
    whole, rest = divmod(t, chunk)
    return whole * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2


def _scan_sizes(cfg: dict):
    return (int(cfg["deployment"]["record_tokens"]), int(cfg["mamba_chunk_size"]),
            int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]),
            int(cfg["mamba_d_state"]), int(cfg["mamba_n_groups"]))


def products(cfg: dict, records: int):
    """(name, m, k, n) of every matrix product of one forward pass over
    ``records`` records: the work the equations need, no masked tile, no
    recomputation."""
    t, q, h, p, s, g = _scan_sizes(cfg)
    rows = records * t
    d_model, d = int(cfg["hidden_size"]), _head_dim(cfg)
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    f = int(cfg["shared_intermediate_size"])
    pairs = records * chunk_pairs(t, q)
    out = []
    for i, kind in enumerate(_layer_kinds(cfg)):
        if kind == "mamba":
            out += [(f"l{i}.in_proj", rows, d_model, 2 * h * p + 2 * g * s + h),
                    (f"l{i}.scan_cb", pairs, g * s, 1),    # C.B^T, visible pairs
                    (f"l{i}.scan_lx", pairs, 1, h * p),    # (C.B^T * L).X
                    (f"l{i}.scan_states", rows, s, h * p),  # B^T.(decay * X)
                    (f"l{i}.scan_cs", rows, s, h * p),      # C.S
                    (f"l{i}.out_proj", rows, h * p, d_model)]
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
    pairs: q and the output once each (Hq heads), k and v once (Hkv heads),
    all in the compute dtype; nothing for a mamba layer."""
    if kind == "mamba":
        return 0.0, 0.0
    t, d = int(cfg["deployment"]["record_tokens"]), _head_dim(cfg)
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    flops = 4.0 * d * records * hq * visible_pairs(t)
    return flops, float(records * t * d * 2 * (hq + hkv) * BF16)


def ssd_cost(cfg: dict, records: int):
    """(FLOPs, least bytes) of one layer's state-space scan over ``records``
    records, whatever computes it: C.B^T and (C.B^T * L).X over the pairs
    inside a chunk, the chunks' states B^T.(decay * X) and C.S over all
    tokens; x in the compute dtype, y out in float32, B and C in the compute
    dtype, the step size in float32, each once."""
    t, q, h, p, s, g = _scan_sizes(cfg)
    pairs = chunk_pairs(t, q)
    flops = records * (2.0 * s * g * pairs + 2.0 * h * p * pairs
                       + 2 * (2.0 * t * h * p * s))
    nbytes = records * t * (h * p * BF16 + h * p * F32 + 2 * g * s * BF16
                            + h * F32)
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
    forward.ssd_cost = lambda: ssd_cost(cfg, traffic.batch)
    forward.layer_kinds = _layer_kinds(cfg)
    return {"optimizer": opt, "forward": forward}


# --------------------------------------------------------------------------
# the comparison with the reference
# --------------------------------------------------------------------------

def _reference():
    """The benchmark's own copy of the reference, loaded as the harness
    loads every file: by name, from this directory's root."""
    from benchmark import run as bench

    return bench.load_module("configs", "granite_4_0_h_micro_reference",
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


def compare(cfg: dict, mix: dict, generator, seed: int, log,
            block_q: int = 512, stand_in: dict = None) -> bool:
    """The system against the reference AT THE STATED PRECISION (float32
    equations whose matrix products round their operands to the
    configuration's compute dtype and sum in float32: the reference's
    ``operands``; its recurrence float32) on one batch of the mix at the
    timed sizes; logs every compared number beside its limit and returns the
    verdict. Limits: ``cfg["correct"]["reference"]``.

    ``stand_in`` is for taking the limits' second readings (PERF.md): the
    reference's own equations take the system's place, changed as the dict
    says. ``{"dtype": "bfloat16"}`` computes them in that dtype throughout
    (operands, sums, results, recurrence, softmax, norms: the nearest
    precision below the stated one); any other key replaces that key of the
    reference's configuration (a planted fault: ``{"state_carried": False}``
    starts every chunk from a zero state, ``{"attention_multiplier": 0.125}``,
    ``{"residual_multiplier": 1.0}``). Each has to come out as not correct."""
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
        params, loss, grads, picked, counters = system_loss_and_grad(
            cfg, x, y, at, seed)
        rparams = decoder_lm.reference_params(params)
        grads = decoder_lm.reference_params(grads)
    else:
        rparams = decoder_lm.reference_params(seeded_parameters(cfg, x, seed))
        changed = {k: v for k, v in stand_in.items() if k != "dtype"}
        low = stand_in.get("dtype")
        with jax.default_matmul_precision("highest"):
            loss, grads, stats, picked = ref.loss_and_grad(
                rparams if low is None else jax.tree_util.tree_map(
                    lambda a: a.astype(low), rparams),
                x, y, {**rcfg, **changed,
                       "operands": None if low else rcfg["operands"]},
                block_q, at)
        loss = float(loss)
        counters = ref.scan_counters(stats, rcfg, *x.shape)
    mark("system")
    # the system's gradients wait on the host while the reference runs
    grads = jax.device_get(jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), grads))
    picked = np.asarray(picked, np.float32)
    mark("system_on_host")
    with jax.default_matmul_precision("highest"):
        rloss, rgrads, stats, rpicked = ref.loss_and_grad(
            rparams, x, y, rcfg, block_q, at)
    rcounters = ref.scan_counters(stats, rcfg, *x.shape)
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
    first = next(i for i, k in enumerate(rcfg["layer_types"]) if k == "mamba")

    def counter_rel(name):
        return abs(counters[name] - rcounters[name]) / max(
            abs(rcounters[name]), 1e-30)

    got = {
        "loss_abs": abs(loss - float(rloss)),
        "logits_abs": float(np.max(np.abs(picked - rpicked))),
        # the tied leaf: both of its uses' gradients, summed
        "grad_rel_l2_embed": grad_err["['embed']"],
        # the first state-space layer's decay, step size and conv: the leaves
        # whose gradients cross the whole scan and every layer above it
        "grad_rel_l2_first_A_log": grad_err[f"['layers'][{first}]['A_log']"],
        "grad_rel_l2_first_dt_bias": grad_err[f"['layers'][{first}]['dt_bias']"],
        "grad_rel_l2_first_conv": grad_err[f"['layers'][{first}]['conv_w']"],
        "grad_rel_l2_worst": grad_err[worst],
        "log_decay_min_rel": counter_rel("ssm_log_decay_min"),
        "state_rms_rel": counter_rel("ssm_state_rms"),
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
