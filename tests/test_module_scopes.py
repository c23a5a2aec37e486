"""Every op of a container-built model's train step has an owner in the
traced program's name stack: the module path from the containers' one seam
(``nn.module.run_child``) and the step part from the step builders
(``model_apply``, ``criterion``, ``optim_update``; in the flat, sharded and
replicated steps also ``param_views``, ``grad_exchange``, ``param_gather``,
``state_sync``). The scopes are metadata: owners under the benchmark's
accepted scope readers, outputs, gradients and the compile count are what
they are without the seam's scope."""

import contextlib
import functools
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.nn import module as nn_module
from bigdl_tpu.optim import SGD, LocalOptimizer, Trigger
from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
from bigdl_tpu.utils.engine import Engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.lib import scope_names, scope_times, scopes  # noqa: E402

BACKWARD = "transpose(jvp("  # what the installed jax writes for the backward
STEP_PARTS = ("model_apply", "criterion", "optim_update")
FLAT_PARTS = ("param_views", "grad_exchange", "param_gather", "state_sync")

_LOC_DEF = re.compile(r'^(#loc\d+) = loc\("([^"]*)"')
_OP = re.compile(r"(stablehlo\.[a-z_]+|call @\S+).*loc\((#loc\d+)\)\s*$")


def op_paths(lowered):
    """[(op, name-stack path)] of every op of a lowered program's text, in
    order. The LOWERED program, not the compiled one: the persistent compile
    cache keys a program without its locations, so a hit hands back the op
    names of whichever compile wrote the entry. An op inside a called
    function (``jit(log_softmax)``) has a path relative to its call site; the
    ``call`` op carries the site's."""
    lines = lowered.as_text(debug_info=True).splitlines()
    names = dict(m.groups() for m in map(_LOC_DEF.match, lines) if m)
    return [(m.group(1), names.get(m.group(2), ""))
            for m in map(_OP.search, lines) if m]


def _step_paths(opt):
    step, specs = opt._step_export_info
    return [p for _, p in op_paths(step.lower(*specs))]


def _holds(paths, name, backward=None):
    """Paths that hold ``name`` as a whole component (the readers' boundary
    rule), forward only / backward only when asked."""
    pat = scope_times._pattern((name,))
    return [p for p in paths if pat.search(p)
            and (backward is None or (BACKWARD in p) == backward)]


# ------------------------------------------------------------ (a) module names

def _graph_model():
    inp = nn.Input()
    trunk = nn.Sequential(
        nn.Linear(12, 16).set_name("fc_a"), nn.ReLU().set_name("act_a"),
        nn.Linear(16, 8).set_name("fc_b")).set_name("trunk").inputs(inp)
    left = nn.Linear(8, 4).set_name("left").inputs(trunk)
    right = nn.Sequential(
        nn.Linear(8, 4).set_name("fc_r"),
        nn.Tanh().set_name("act_r")).set_name("right").inputs(trunk)
    out = nn.LogSoftMax().set_name("logp").inputs(
        nn.CAddTable().set_name("join").inputs(left, right))
    return nn.Graph([inp], [out])


def _fit(opt, steps=3):
    opt.set_optim_method(SGD(learningrate=0.1, momentum=0.9))
    opt.set_end_when(Trigger.max_iteration(steps))
    opt.optimize()
    return opt


def _data(n=32, d=12, classes=4, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    return DataSet.array(rng.standard_normal((n, d)).astype(np.float32),
                         rng.integers(0, classes, n), batch_size=batch)


@pytest.fixture(scope="module")
def graph_step_paths():
    opt = _fit(LocalOptimizer(_graph_model(), _data(), nn.ClassNLLCriterion()))
    return _step_paths(opt)


@pytest.mark.parametrize("path", [
    "trunk/fc_a", "trunk/act_a", "trunk/fc_b", "left", "right/fc_r",
    "right/act_r", "logp"])
def test_a_sequential_in_a_graph_names_each_childs_ops(graph_step_paths, path):
    leaf = path.rsplit("/", 1)[-1]
    fwd = _holds(graph_step_paths, leaf, backward=False)
    bwd = _holds(graph_step_paths, leaf, backward=True)
    assert fwd and bwd, (path, len(fwd), len(bwd))
    # the whole module path, under the step part, forward and backward
    assert any(f"jvp(model_apply)/{path}/" in p for p in fwd), fwd[:3]
    assert any(f"{BACKWARD}model_apply))/{path}/" in p for p in bwd), bwd[:3]


def test_a_module_without_a_backward_of_its_own_is_named_forward(
        graph_step_paths):
    # an add's transpose is the identity: nothing to name
    assert _holds(graph_step_paths, "join", backward=False)
    assert not _holds(graph_step_paths, "join", backward=True)


def test_the_module_scopes_come_from_one_helper():
    """No container calls a child around the seam."""
    pat = re.compile(r"\._apply\(\s*params\[")
    found = []
    for sub in ("nn", "models"):
        for base, _, files in os.walk(os.path.join(ROOT, "bigdl_tpu", sub)):
            for f in files:
                if f.endswith(".py"):
                    with open(os.path.join(base, f)) as fh:
                        if pat.search(fh.read()):
                            found.append(os.path.join(base, f))
    assert not found


# --------------------------------------------------------------- (b) step parts

@pytest.mark.parametrize("part", STEP_PARTS)
def test_local_step_holds_the_step_parts(graph_step_paths, part):
    assert _holds(graph_step_paths, part)
    if part != "optim_update":
        assert _holds(graph_step_paths, part, backward=True)
        assert _holds(graph_step_paths, part, backward=False)
    else:  # nothing differentiates the update
        assert not _holds(graph_step_paths, part, backward=True)


def test_every_op_of_the_local_step_has_a_part(graph_step_paths):
    parts = scope_times._pattern(STEP_PARTS + FLAT_PARTS)
    # (a called function's own ops are relative to their call site's path)
    unowned = [p for p in graph_step_paths
               if p.startswith("jit(") and not parts.search(p)]
    # what is left: the step's own plumbing (none of it the model's)
    assert len(unowned) < 0.1 * len(graph_step_paths), unowned[:10]
    assert not [p for p in unowned if "fc_" in p or "trunk" in p]


def test_local_flat_step_holds_param_views():
    opt = LocalOptimizer(_graph_model(), _data(), nn.ClassNLLCriterion(),
                         flat_update=True)
    paths = _step_paths(_fit(opt))
    for part in STEP_PARTS + ("param_views",):
        assert _holds(paths, part), part
    # the views' transpose assembles the flat gradient
    assert _holds(paths, "param_views", backward=True)


@pytest.fixture(scope="module")
def distri_paths():
    out = {}
    Engine.reset()
    Engine.init()
    try:
        for sync in ("sharded", "replicated"):
            data = DataSet.distributed(_data(n=64, batch=16), 8)
            opt = DistriOptimizer(_graph_model(), data, nn.ClassNLLCriterion(),
                                  parameter_sync=sync)
            out[sync] = _step_paths(_fit(opt))
    finally:
        Engine.reset()
    return out


@pytest.mark.parametrize("sync,parts", [
    ("sharded", STEP_PARTS + FLAT_PARTS),
    ("replicated", STEP_PARTS + ("grad_exchange", "state_sync"))])
def test_distributed_steps_hold_their_parts(distri_paths, sync, parts):
    paths = distri_paths[sync]
    for part in parts:
        assert _holds(paths, part), (sync, part)
    collectives = {
        "sharded": {"grad_exchange": "reduce_scatter",
                    "param_gather": "all_gather"},
        "replicated": {"grad_exchange": "psum"}}[sync]
    for part, primitive in collectives.items():
        assert any(p.rsplit("/", 1)[-1].startswith(primitive)
                   for p in _holds(paths, part)), (sync, part)
    # the module names are there too, forward and backward
    assert _holds(paths, "fc_a", backward=False)
    assert _holds(paths, "fc_a", backward=True)


# -------------------------------- (c), (d) the accepted readers' owners, bit for bit

@functools.lru_cache(maxsize=None)
def _load_test_module(name):
    spec = importlib.util.spec_from_file_location(
        "_scopes_" + name, os.path.join(ROOT, "tests", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _decoder():
    t = _load_test_module("test_decoder_lm")
    config = {**t.CONFIG, "num_hidden_layers": 4,
              "layer_types": t.PERIOD, "experts_held": [0, 1, 2, 3]}
    return t, config, nn.TokenCrossEntropyCriterion(), scopes.SCOPES


def _hybrid():
    t = _load_test_module("test_hybrid_lm")
    return (t, dict(t.CONFIG), nn.TokenCrossEntropyCriterion(),
            scopes.SCOPES + scope_times.HYBRID_SCOPES)


def _latent():
    t = _load_test_module("test_latent_moe_lm")
    config = {**t.CONFIG, "experts_held": [0, 1, 2, 5]}
    return (t, config, nn.MultiTokenCrossEntropyCriterion(t.WEIGHT),
            scope_names.LATENT_SCOPES + ("mtp",))


LMS = {"decoder": _decoder, "hybrid": _hybrid, "latent": _latent}


@functools.lru_cache(maxsize=None)
def _lm(kind):
    t, config, criterion, names = LMS[kind]()
    model, params, state = t._built(config)
    x, y = t._tokens(1)
    opt = LocalOptimizer(
        model, DataSet.array(np.asarray(x), np.asarray(y), batch_size=t.N),
        criterion)
    fn = jax.value_and_grad(opt._loss_fn, has_aux=True)
    return fn, (params, state, x, y, jax.random.PRNGKey(0)), names


@functools.lru_cache(maxsize=None)
def _lm_lowered(kind):
    """The tiny model's loss + gradients lowered with the seam's scope and
    without it."""
    fn, args, _ = _lm(kind)
    out = []
    for seam in (True, False):
        with pytest.MonkeyPatch.context() as mp:
            if not seam:
                mp.setattr(nn_module, "child_scope",
                           lambda m: contextlib.nullcontext())
            # a fresh function: a traced program is cached by identity
            out.append(jax.jit(lambda *a: fn(*a)).lower(*args))
    return out


def _owners(paths, names):
    pat = scope_times._pattern(names)
    out = []
    for p in paths:
        found = pat.findall(p)
        out.append(found[-1] if found else None)
    return out


@pytest.mark.parametrize("kind", sorted(LMS))
def test_accepted_readers_owners_do_not_move(kind):
    """An op's owner under the accepted readers' names (the last name found)
    is what it is without the seam's scope: ``embed``, ``mlp`` and ``mtp``
    are module names AND reader names, and each module scope wraps exactly
    what the inner scope of that name wraps."""
    names = _lm(kind)[2]
    with_seam, without = map(op_paths, _lm_lowered(kind))
    assert [o for o, _ in with_seam] == [o for o, _ in without]
    assert any("/layer_0/block/" in p for _, p in with_seam)
    assert not any("/layer_0/block/" in p for _, p in without)
    a = _owners([p for _, p in with_seam], names)
    b = _owners([p for _, p in without], names)
    moved = [(p, x, y) for (_, p), x, y in zip(with_seam, a, b) if x != y]
    assert not moved, moved[:5]
    # "mtp" alone, as its reader looks for it
    assert _owners([p for _, p in with_seam], ("mtp",)) == _owners(
        [p for _, p in without], ("mtp",))
    for (_, p), (_, q) in zip(with_seam, without):  # and the backward's marker
        assert (BACKWARD in p) == (BACKWARD in q)


@pytest.mark.parametrize("kind", sorted(LMS))
def test_outputs_and_gradients_are_bit_identical_without_the_seam(kind):
    """The two programs are one program: the same text once the locations
    are left out (which is also the persistent cache's key: a profile of a
    cached step shows the names of the compile that wrote the entry), and
    the same bytes out."""
    with_seam, without = _lm_lowered(kind)
    assert with_seam.as_text() == without.as_text()
    assert with_seam.as_text(debug_info=True) != without.as_text(
        debug_info=True)
    args = _lm(kind)[1]
    (loss, state), grads = with_seam.compile()(*args)
    (loss0, state0), grads0 = without.compile()(*args)
    assert np.asarray(loss).tobytes() == np.asarray(loss0).tobytes()
    for a, b in zip(jax.tree_util.tree_leaves((state, grads)),
                    jax.tree_util.tree_leaves((state0, grads0))):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_the_backward_marker_is_what_this_jax_prints():
    m = nn.Sequential(nn.Linear(3, 2).set_name("fc"))
    m.build(jax.random.PRNGKey(0), jax.ShapeDtypeStruct((2, 3), jnp.float32))

    def loss(p):
        return jnp.sum(m.apply(p, m.get_state(), jnp.ones((2, 3)),
                               training=True)[0] ** 2)

    paths = [p for _, p in op_paths(jax.jit(jax.value_and_grad(loss)).lower(
        m.get_parameters()))]
    assert any(p.startswith(f"jit(loss)/{BACKWARD}fc))/") for p in paths)
    assert any(p.startswith("jit(loss)/jvp(fc)/") for p in paths)


def test_eager_forward_enters_the_same_seam(monkeypatch):
    seen = []
    real = nn_module.child_scope
    monkeypatch.setattr(nn_module, "child_scope",
                        lambda m: seen.append(m.name()) or real(m))
    m = nn.Sequential(nn.Linear(3, 2).set_name("fc"), nn.ReLU().set_name("act"))
    m.forward(np.ones((2, 3), np.float32))
    assert seen[-2:] == ["fc", "act"]


# ------------------------------------------------------------- (e) one compile

def test_one_compile_of_the_train_step_as_before():
    from bigdl_tpu.obs import Telemetry

    opt = LocalOptimizer(_graph_model(), _data(), nn.ClassNLLCriterion())
    keep = _load_test_module("test_decoder_lm")._Keep()
    tel = Telemetry(exporters=[keep])
    opt.set_telemetry(tel)
    _fit(opt, steps=6)
    tel.close()
    steps = [r for r in keep.records if r.get("type") == "step"]
    assert len(steps) == 6 and steps[-1]["compile_count"] == 1
    assert sum(r["count"] for r in keep.records
               if r.get("type") == "compile") == 1
