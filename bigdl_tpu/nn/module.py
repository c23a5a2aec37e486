"""Module system core — BigDL's ``AbstractModule`` re-designed TPU-first.

Reference behavior (SURVEY.md §2.2): ``$DL/nn/abstractnn/AbstractModule.scala``
(AbstractModule) is the base of every layer: ``forward``/``backward`` caching
``output``/``gradInput``, ``accGradParameters`` into hand-allocated gradient buffers,
``parameters()``, training/eval mode, a name registry. Every one of ~300 layers
hand-writes its backward pass.

TPU-native design — the central architectural decision of this framework:

* Every module is, at its core, a **pure function**
  ``_apply(params, state, x, training, rng) -> (y, new_state)`` over pytrees. This is
  what ``jax.jit`` traces: the whole model collapses to one XLA computation (the role
  the reference needed an entire second engine for — ``nn.mkldnn.DnnGraph`` compile +
  ReorderMemory + Fusion are all replaced by XLA's own fusion/layout pass).
* Hand-written backward code does not exist: ``backward`` is derived with ``jax.vjp``
  over the pure apply. The BigDL API (``backward`` returns gradInput and accumulates
  parameter gradients) is preserved as a façade for parity and for oracle tests.
* Parameters and mutable layer state (BN running stats, RNN hidden carry) live in
  explicit pytrees, nested ``{child_name: {...}}`` through containers, so the
  optimizer can jit one train step over ``(params, state, batch)`` and shard it with
  ``pjit``/``shard_map`` without touching module code.
* Randomness is an explicit key; each module derives its own stream inside the trace
  with ``fold_in(rng, module_uid)`` — deterministic, replay-able (the reference's
  per-thread stateful MKL-VSL RNG has no jit-compatible analog).
"""

from __future__ import annotations

import functools
import itertools
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.random import RandomGenerator

_uid_counter = itertools.count(1)
_uid_lock = threading.Lock()


def _next_uid() -> int:
    with _uid_lock:
        return next(_uid_counter)


def _to_spec(x):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype)
        if not isinstance(a, jax.ShapeDtypeStruct)
        else a,
        x,
    )


def _as_jnp(x):
    return jax.tree_util.tree_map(jnp.asarray, x)


def child_scope(m: "AbstractModule"):
    """The name a child's ops carry in the traced program: its key in the
    parameter tree, so a profile reads as a checkpoint does."""
    return jax.named_scope(m.name())


def run_child(m: "AbstractModule", params, state, x, training, rng):
    """The ONE seam at which a container runs a child:
    ``m._apply(params, state, x, training, rng)`` inside
    ``jax.named_scope(m.name())``. ``params`` and ``state`` are the child's
    own subtrees. Every container (``Container._child_apply``, ``Graph``'s
    node loop, the containers that call a child directly) comes through here,
    so every device op of a container-built model carries its module path
    (``.../res2a_b1/res2a_b1_conv/conv_general_dilated``; JAX marks the
    backward's copy ``transpose(jvp(...))`` itself). The scope is metadata on
    the traced program, not an instruction: always on, no switch. Leaf
    modules are not touched."""
    with child_scope(m):
        return m._apply(params, state, x, training, rng)


# --- ctor/build recording for topology serialization (utils/module_serializer) ---
# The reference's ModuleSerializer reconstructs each layer reflectively from its
# serialized fields ($DL/utils/serializer, SURVEY.md §2.7); here every subclass
# records its constructor arguments and the top-level build spec automatically,
# so ``save_module`` can persist topology and ``load_module`` can rebuild the
# model in a fresh process.

_build_depth = threading.local()


def _record_ctor(init):
    @functools.wraps(init)
    def wrapper(self, *args, **kwargs):
        if not hasattr(self, "_ctor_spec"):  # most-derived class wins
            self._ctor_spec = (args, dict(kwargs))
        init(self, *args, **kwargs)

    wrapper._ctor_recorded = True
    return wrapper


def _record_build(build):
    @functools.wraps(build)
    def wrapper(self, rng, in_spec):
        depth = getattr(_build_depth, "d", 0)
        if depth == 0:  # only the outermost build call is the model's input spec
            self._top_in_spec = in_spec
        _build_depth.d = depth + 1
        try:
            out = build(self, rng, in_spec)
        finally:
            _build_depth.d = depth
        # single choke point for rebuild invalidation: every ``build`` override
        # (Sequential, Graph, NeuralCF, FPN, ...) is wrapped here, so a rebuild
        # always drops jit caches keyed on this object (validate()'s eval step)
        self._invalidate_jit_caches()
        return out

    wrapper._build_recorded = True
    return wrapper


class AbstractModule:
    """Base class of every layer and container.

    Subclasses implement two hooks:

    * ``_build(rng, in_spec) -> (params, state)`` — allocate this module's own
      parameter/state dicts given an input ``ShapeDtypeStruct`` pytree.
    * ``_apply(params, state, x, training, rng) -> (y, new_state)`` — the pure
      forward. Must be trace-friendly: no data-dependent Python control flow.

    The stateful Torch-style API (``forward``/``backward``/``parameters``) is provided
    on top and is what user code and oracle tests exercise; the pure API is what the
    optimizers jit.
    """

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        init = cls.__dict__.get("__init__")
        if init is not None and not getattr(init, "_ctor_recorded", False):
            cls.__init__ = _record_ctor(init)
        bld = cls.__dict__.get("build")
        if bld is not None and not getattr(bld, "_build_recorded", False):
            cls.build = _record_build(bld)

    def __init__(self):
        self._uid: int = _next_uid()
        self._name: Optional[str] = None
        self.train_mode: bool = True
        self.output: Any = None
        self.grad_input: Any = None
        self._built: bool = False
        self._params: Dict[str, Any] = {}
        self._state: Dict[str, Any] = {}
        self._grads: Dict[str, Any] = {}
        self._last_rng: Optional[jax.Array] = None
        # state snapshot taken before the last forward; backward must linearize the
        # same computation that produced the cached output, not the mutated state
        self._last_state: Optional[Dict[str, Any]] = None
        # scalar multipliers applied to param grads (reference: setScaleW/setScaleB)
        self.scale_w: float = 1.0
        self.scale_b: float = 1.0

    # ------------------------------------------------------------------ names
    def name(self) -> str:
        return self._name or f"{type(self).__name__}{self._uid}"

    def set_name(self, name: str) -> "AbstractModule":
        self._name = name
        return self

    def get_name(self) -> str:
        return self.name()

    # --------------------------------------------------------------- building
    def _build(self, rng: jax.Array, in_spec) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        return {}, {}

    # ----------------------------------------------------------- shape contract
    def infer_shape(self, in_spec):
        """Static shape/dtype contract: input spec pytree -> output spec pytree.

        Implementations must not execute the model or allocate parameters, and
        must raise ``ValueError`` with a readable message (both offending
        shapes) on a contract violation. The base returns ``NotImplemented``,
        meaning "no analytic contract" — ``infer_module_shape`` then falls back
        to a ``jax.eval_shape`` abstract trace of build + apply.
        """
        return NotImplemented

    def _infer_shape_via_apply(self, in_spec):
        """Contract for parameter-less layers whose ``_apply`` is shape-complete
        with empty params: abstract-trace the layer's own apply. Exact by
        construction (it is the same computation ``jax.eval_shape`` sees)."""
        return jax.eval_shape(
            lambda xx: self._apply({}, {}, xx, False, None)[0], in_spec
        )

    def _apply(self, params, state, x, training: bool, rng):  # pragma: no cover
        raise NotImplementedError

    def is_built(self) -> bool:
        return self._built

    def _invalidate_jit_caches(self) -> None:
        # a (re)build can change the traced structure — drop any jit caches
        # keyed on this object (validate() caches its eval step here)
        if hasattr(self, "_jit_eval_step"):
            del self._jit_eval_step

    def build(self, rng: jax.Array, in_spec):
        """Allocate params/state for this subtree; return the output spec."""
        params, state = self._build(rng, in_spec)
        self._params = params
        self._state = state
        self._grads = None  # zeros like the parameters, when first asked for
        self._built = True
        out_spec = jax.eval_shape(
            lambda p, s, xx: self._apply(p, s, xx, False, None)[0], params, state, in_spec
        )
        return out_spec

    def init(self, rng: Optional[jax.Array] = None, sample_input=None):
        """Explicitly initialize; returns (params, state) pytrees for functional use."""
        if rng is None:
            rng = RandomGenerator.next_key()
        if sample_input is not None:
            self.build(rng, _to_spec(sample_input))
        elif not self._built:
            raise ValueError(
                f"{self.name()}: init() needs a sample_input the first time"
            )
        return self.get_parameters(), self.get_state()

    def _ensure_built(self, x) -> None:
        if not self._built:
            self.build(RandomGenerator.next_key(), _to_spec(x))

    # ---------------------------------------------------------- forward hooks
    def register_forward_hook(self, hook) -> "ForwardHookHandle":
        """Wrap THIS module's pure forward: after every ``_apply`` (any call
        site — root ``apply``, container ``_child_apply``, Graph nodes),
        ``hook(module, x, y)`` runs inside the same trace; a returned dict is
        merged into the new state pytree (the jit-compatible side channel —
        the observability layer's activation probes stash their statistics
        this way, ``obs/health.py``).

        Hooks must be pure/trace-friendly (jnp only — no host syncs, no
        Python side effects that matter per step: under ``jit`` the hook body
        runs once at trace time). Install AFTER build and keep the returned
        state keys zero-seeded in ``_state`` before the first traced call, or
        the changed state structure retraces the step. Returns a handle whose
        ``remove()`` restores the previous forward."""
        prev = self.__dict__.get("_apply")  # None = class-level _apply
        inner = self._apply  # current (possibly already-hooked) forward

        def _hooked_apply(params, state, x, training, rng):
            y, new_state = inner(params, state, x, training, rng)
            extra = hook(self, x, y)
            if extra is not None:
                new_state = dict(new_state)
                new_state.update(extra)
            return y, new_state

        self._apply = _hooked_apply
        self._invalidate_jit_caches()  # a cached eval step misses the hook
        return ForwardHookHandle(self, _hooked_apply, prev)

    # ------------------------------------------------------------- functional
    def apply(self, params, state, x, *, training: bool = False, rng=None):
        """Pure forward over explicit pytrees. What ``jit`` traces."""
        return self._apply(params, state, x, training, rng)

    def apply_fn(self, *, training: bool = False) -> Callable:
        """Convenience: a jit-friendly ``f(params, state, x, rng)`` closure."""

        def f(params, state, x, rng=None):
            return self._apply(params, state, x, training, rng)

        return f

    # ---------------------------------------------------------- param pytrees
    def get_parameters(self) -> Dict[str, Any]:
        return self._params

    def set_parameters(self, params: Dict[str, Any]) -> None:
        self._params = params

    def get_state(self) -> Dict[str, Any]:
        return self._state

    def set_state(self, state: Dict[str, Any]) -> None:
        self._state = state

    @property
    def _grads(self) -> Dict[str, Any]:
        """The stateful API's gradient accumulators (``backward`` adds into
        them, ``parameters()`` lists them): zeros in the parameters' shapes,
        made when first asked for. A model that trains through an optimizer
        never asks, and so does not hold a dead copy of its parameters' size
        on the device (3.09 GB for the 772M-parameter language model, which
        with it did not fit beside its step's temporaries; PERF.md, PR 32)."""
        if self._grads_made is None:
            self._grads_made = jax.tree_util.tree_map(jnp.zeros_like,
                                                      self._params)
        return self._grads_made

    @_grads.setter
    def _grads(self, grads: Optional[Dict[str, Any]]) -> None:
        self._grads_made = grads

    def get_grad_parameters(self) -> Dict[str, Any]:
        return self._grads

    def set_grad_parameters(self, grads: Dict[str, Any]) -> None:
        self._grads = grads

    def parameters(self) -> Tuple[List[jax.Array], List[jax.Array]]:
        """BigDL parity: (weights, gradWeights) as flat leaf lists.

        Reference: ``AbstractModule.parameters()`` returns parallel arrays of weight
        and gradient tensors ($DL/nn/abstractnn/AbstractModule.scala).
        """
        w = jax.tree_util.tree_leaves(self.get_parameters())
        g = jax.tree_util.tree_leaves(self.get_grad_parameters())
        return w, g

    def get_parameters_table(self) -> Dict[str, Dict[str, Any]]:
        """name → own-param dict for every parameterized module in the subtree."""
        return {m.name(): m._params for m in self.walk() if m._params}

    def zero_grad_parameters(self) -> None:
        self.set_grad_parameters(
            jax.tree_util.tree_map(jnp.zeros_like, self.get_parameters())
        )

    def n_parameters(self) -> int:
        return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self.get_parameters()))

    # ------------------------------------------------------------ train state
    def training(self) -> "AbstractModule":
        self.train_mode = True
        return self

    def evaluate(self, dataset=None, methods=None, batch_size=None):
        """No args: switch to eval mode (reference ``evaluate()``). With a dataset
        and validation methods: run distributed evaluation and return results
        (reference ``evaluate(rdd, Array(Top1Accuracy()))``, $DL/optim/Evaluator)."""
        self.train_mode = False
        if dataset is None:
            return self
        from ..optim.predictor import Evaluator

        return Evaluator(self, batch_size).evaluate(dataset, methods)

    def is_training(self) -> bool:
        return self.train_mode

    # --------------------------------------------------------------- stateful
    def forward(self, x):
        """Stateful forward: caches ``output``; threads RNG + running state."""
        x = _as_jnp(x)
        self._ensure_built(x)
        rng = RandomGenerator.next_key() if self.train_mode else None
        self._last_rng = rng
        self._last_state = self.get_state()
        y, new_state = self._apply(
            self.get_parameters(), self._last_state, x, self.train_mode, rng
        )
        if self.train_mode:
            self.set_state(new_state)
        self.output = y
        return y

    def __call__(self, x):
        return self.forward(x)

    def update_output(self, x):
        return self.forward(x)

    def backward(self, x, grad_output):
        """gradInput via VJP; accumulates parameter grads (BigDL semantics).

        Equivalent of the reference's ``updateGradInput`` + ``accGradParameters``
        double pass — derived, not hand-written. Uses the same RNG as the preceding
        ``forward`` so dropout masks and other sampled values match.
        """
        x = _as_jnp(x)
        self._ensure_built(x)
        params = self.get_parameters()
        state = self._last_state if self._last_state is not None else self.get_state()
        rng = self._last_rng

        def f(p, xx):
            return self._apply(p, state, xx, self.train_mode, rng)[0]

        _, vjp = jax.vjp(f, params, x)
        gp, gx = vjp(_as_jnp(grad_output))
        # setScaleW/setScaleB parity: scale bias-named leaves by scale_b, the rest by
        # scale_w. (Applied with this module's scales; per-child scales inside a
        # container backward are not tracked — set scales on the module you call
        # backward on.)
        if self.scale_w != 1.0 or self.scale_b != 1.0:
            gp = jax.tree_util.tree_map_with_path(
                lambda path, a: a
                * (
                    self.scale_b
                    if any(getattr(k, "key", None) == "bias" for k in path)
                    else self.scale_w
                ),
                gp,
            )
        self.set_grad_parameters(
            jax.tree_util.tree_map(lambda acc, new: acc + new, self.get_grad_parameters(), gp)
        )
        self.grad_input = gx
        return gx

    def update_grad_input(self, x, grad_output):
        """gradInput only (no param-grad accumulation)."""
        x = _as_jnp(x)
        self._ensure_built(x)
        params, rng = self.get_parameters(), self._last_rng
        state = self._last_state if self._last_state is not None else self.get_state()

        def f(xx):
            return self._apply(params, state, xx, self.train_mode, rng)[0]

        _, vjp = jax.vjp(f, x)
        (gx,) = vjp(_as_jnp(grad_output))
        self.grad_input = gx
        return gx

    def acc_grad_parameters(self, x, grad_output) -> None:
        self.backward(x, grad_output)

    def walk(self):
        """Yield this module and (for containers) every descendant."""
        yield self

    def regularization_loss_tree(self, params):
        """Sum of per-layer regularizer penalties over this subtree (pure).

        Reference applies regularizers inside each layer's accGradParameters;
        here the penalty joins the jitted loss so autodiff produces the same
        gradient contribution.
        """
        if hasattr(self, "regularization_loss"):
            return self.regularization_loss(params)
        return 0.0

    def auxiliary_loss_tree(self, state):
        """Sum of input-dependent auxiliary losses a forward pass stashed in
        the state pytree under ``'_aux_loss'`` keys (e.g. the MoE router's
        load-balancing term). Optimizers fold this into the objective the
        same way they fold ``regularization_loss_tree`` — the state pytree
        is the jit-compatible channel for activations-derived penalties."""
        total = 0.0

        def walk(s):
            nonlocal total
            if isinstance(s, dict):
                for k, v in s.items():
                    if k == "_aux_loss":
                        total = total + v
                    else:
                        walk(v)

        walk(state)
        return total

    def counters_tree(self, state) -> Dict[str, Any]:
        """Counters a forward pass stashed in the state pytree under
        ``'_counters'`` keys (``{name: scalar}``, e.g. the routed experts'
        ``moe_pairs_local``), reduced over the modules that wrote them: a name
        with ``max`` in it by maximum, one with ``min`` by minimum, any other
        by sum. The standard train
        step hands them out as one more output, pulled with the one-step-late
        loss and written into the telemetry step record under their names
        (docs/observability.md); ``{}`` for a model that keeps none."""
        found: Dict[str, Any] = {}

        def walk(s):
            if not isinstance(s, dict):
                return
            for k, v in s.items():
                if k != "_counters":
                    walk(v)
                    continue
                for name, value in v.items():
                    if name not in found:
                        found[name] = value
                    elif "max" in name:
                        found[name] = jnp.maximum(found[name], value)
                    elif "min" in name:
                        found[name] = jnp.minimum(found[name], value)
                    else:
                        found[name] = found[name] + value

        walk(state)
        return found

    def with_counters(self, state, counted: Dict[str, Any]):
        """``state`` with each of ``counted``'s values in the ``_counters``
        slot of its name (a criterion's parts: ``AbstractCriterion.counted``).
        The model declares the slots, so the state's structure is its own; a
        name with no slot is an error, not a silent drop."""
        left = set(counted)

        def walk(s):
            if not isinstance(s, dict):
                return s
            out = {}
            for k, v in s.items():
                if k == "_counters":
                    left.difference_update(v)
                    v = {n: counted.get(n, c) for n, c in v.items()}
                out[k] = v if k == "_counters" else walk(v)
            return out

        state = walk(state)
        if left:
            raise ValueError(
                f"the criterion reports {sorted(left)} but {self.name()}'s "
                "state has no '_counters' slot of that name")
        return state

    # -------------------------------------------------------------- inference
    def predict(self, data, batch_size: Optional[int] = None):
        """Batched forward over a DataSet / array / list of Samples, reusing one
        jit-compiled apply (reference: ``model.predict(rdd)``)."""
        from ..optim.predictor import Predictor

        return Predictor(self, batch_size).predict(data)

    def predict_class(self, data, batch_size: Optional[int] = None):
        """1-based argmax class per record (reference: ``predictClass``)."""
        from ..optim.predictor import Predictor

        return Predictor(self, batch_size).predict_class(data)

    def quantize(self, dtype: str = "int8") -> "AbstractModule":
        """Rewrite this (built) module tree with quantized inference layers
        (reference: ``AbstractModule.quantize`` → nn/quantized/Quantization).
        ``dtype``: ``"int8"`` (default) or ``"fp8"`` (per-output-channel
        float8 weights — the serving fp8 tier)."""
        from .quantized import quantize

        return quantize(self, dtype=dtype)

    # ------------------------------------------------------------ persistence
    def save_module(self, path: str, overwrite: bool = True) -> None:
        """Persist TOPOLOGY + params + state as one npz (reference:
        ``Module.saveModule`` writing the versioned protobuf model file) —
        reloadable in a fresh process via ``nn.load_module(path)``. Falls back
        to arrays-only when the topology can't be captured (exotic ctor args),
        which stays loadable into a rebuilt module via instance
        ``load_module``."""
        import os

        from ..utils.serialization import save_pytree

        if not overwrite and os.path.exists(path):
            raise FileExistsError(path)
        if not self.is_built():
            raise ValueError("save_module: module not built yet")
        from ..utils.module_serializer import save_module_def

        try:
            save_module_def(path, self)
        except (TypeError, ValueError):
            save_pytree(
                path, {"params": self.get_parameters(), "state": self.get_state()}
            )

    def load_module(self, path: str) -> "AbstractModule":
        """Load arrays saved by ``save_module`` into this (built) module
        (reference: ``Module.loadModule``)."""
        from ..utils.serialization import load_pytree

        if not self.is_built():
            raise ValueError(
                "load_module: build the module first (init with a sample input)"
            )
        blob = load_pytree(
            path, like={"params": self.get_parameters(), "state": self.get_state()}
        )
        self.set_parameters(_as_jnp(blob["params"]))
        self.set_state(_as_jnp(blob["state"]))
        return self

    # ------------------------------------------------------------------- misc
    def reset(self) -> None:
        """Mark for re-initialization: the next ``forward`` re-samples parameters.

        Lazy by design (building needs an input spec); the reference's eager
        ``AbstractModule.reset`` re-samples immediately because its layers know
        their shapes up front.
        """
        self._built = False

    def clone(self) -> "AbstractModule":
        import copy

        return copy.deepcopy(self)

    def __repr__(self):
        return f"{type(self).__name__}({self.name()})"


# the base build is used directly by every leaf module; wrap it for spec recording
AbstractModule.build = _record_build(AbstractModule.build)


class ForwardHookHandle:
    """Undo token for :meth:`AbstractModule.register_forward_hook` — LIFO
    removal restores the exact pre-hook forward (instance-level wrapper or
    the class method)."""

    __slots__ = ("_module", "_wrapped", "_prev")

    def __init__(self, module, wrapped, prev):
        self._module, self._wrapped, self._prev = module, wrapped, prev

    def remove(self) -> None:
        m = self._module
        if m.__dict__.get("_apply") is not self._wrapped:
            return  # a later hook wrapped on top (or already removed)
        if self._prev is None:
            m.__dict__.pop("_apply", None)
        else:
            m._apply = self._prev
        m._invalidate_jit_caches()


def infer_module_shape(module: AbstractModule, in_spec):
    """Static out-spec of ``module`` for ``in_spec``, without running the model.

    Resolution order: the module's own ``infer_shape`` contract; for built
    modules, ``jax.eval_shape`` over the pure apply with spec'd params; for
    unbuilt modules, ``jax.eval_shape`` over ``build`` with an ABSTRACT key, so
    no parameter array is materialized (the random initializers trace through),
    and the module's pre-call state is restored afterwards.
    """
    out = module.infer_shape(in_spec)
    if out is not NotImplemented:
        return out
    if module.is_built():
        return jax.eval_shape(
            lambda p, s, xx: module._apply(p, s, xx, False, None)[0],
            _to_spec(module.get_parameters()),
            _to_spec(module.get_state()),
            in_spec,
        )
    # snapshot the subtree: the abstract build stores tracers into _params,
    # flips _built, may bind config attributes to THIS spec (Linear.input_size,
    # RnnCell.input_size, ...), and may create children sized to it (Highway
    # with size=None, keras wrappers). Roll back each module's full __dict__
    # (shallow) plus a copy of container child lists, so a later real build
    # with a different spec starts clean.
    before = {id(m): dict(m.__dict__) for m in module.walk()}
    before_children = {
        id(m): list(m.modules)
        for m in module.walk()
        if isinstance(m, Container)
    }
    key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    try:
        return jax.eval_shape(lambda k: module.build(k, in_spec), key_spec)
    finally:
        # materialize before mutating: restoring a container's child list while
        # its walk() generator is live would skip subtrees
        polluted = list(module.walk())
        for m in polluted:
            saved = before.get(id(m))
            if saved is None:
                # created during the abstract trace and now detached
                m._params, m._state, m._grads, m._built = {}, {}, {}, False
            else:
                m.__dict__.clear()
                m.__dict__.update(saved)
        for m in polluted:
            kids = before_children.get(id(m))
            if kids is not None:
                m.modules = kids


class Container(AbstractModule):
    """Module with submodules (reference: ``$DL/nn/Container.scala``).

    Params/state/grads of a container are nested dicts keyed by child name; the
    container itself owns none.
    """

    def __init__(self, *modules: AbstractModule):
        super().__init__()
        self.modules: List[AbstractModule] = []
        for m in modules:
            self.add(m)

    def add(self, module: AbstractModule) -> "Container":
        if not isinstance(module, AbstractModule):
            raise TypeError(f"expected AbstractModule, got {type(module)}")
        if module._name is None:
            # Deterministic per-container child names (<Type>_<index>): checkpoint
            # pytree keys must be stable across processes and instance counts —
            # uid-based names are not (SURVEY.md §7 risk (f), format stability).
            module.set_name(f"{type(module).__name__}_{len(self.modules)}")
        names = {m.name() for m in self.modules}
        if module.name() in names:
            raise ValueError(f"duplicate child name {module.name()!r}")
        self.modules.append(module)
        return self

    def __getitem__(self, i: int) -> AbstractModule:
        return self.modules[i]

    def __len__(self) -> int:
        return len(self.modules)

    # containers aggregate child pytrees
    def get_parameters(self):
        return {m.name(): m.get_parameters() for m in self.modules}

    def set_parameters(self, params) -> None:
        for m in self.modules:
            m.set_parameters(params[m.name()])

    def get_state(self):
        return {m.name(): m.get_state() for m in self.modules}

    def set_state(self, state) -> None:
        for m in self.modules:
            m.set_state(state[m.name()])

    def get_grad_parameters(self):
        return {m.name(): m.get_grad_parameters() for m in self.modules}

    def set_grad_parameters(self, grads) -> None:
        for m in self.modules:
            m.set_grad_parameters(grads[m.name()])

    def training(self):
        super().training()
        for m in self.modules:
            m.training()
        return self

    def evaluate(self, dataset=None, methods=None, batch_size=None):
        self.train_mode = False
        for m in self.modules:
            m.evaluate()
        if dataset is None:
            return self
        return super().evaluate(dataset, methods, batch_size)

    def walk(self):
        yield self
        for m in self.modules:
            yield from m.walk()

    def regularization_loss_tree(self, params):
        total = 0.0
        for m in self.modules:
            total = total + m.regularization_loss_tree(params[m.name()])
        return total

    def _child_apply(self, m: AbstractModule, x, training, rng, params, state, new_state):
        y, s = run_child(m, params[m.name()], state[m.name()], x, training, rng)
        new_state[m.name()] = s
        return y

    def __repr__(self):
        inner = ",\n  ".join(repr(m) for m in self.modules)
        return f"{type(self).__name__}(\n  {inner}\n)"


class Sequential(Container):
    """Linear chain container (reference: ``$DL/nn/Sequential.scala``)."""

    def build(self, rng, in_spec):
        spec = in_spec
        for i, m in enumerate(self.modules):
            spec = m.build(jax.random.fold_in(rng, i), spec)
        self._built = True
        return spec

    def infer_shape(self, in_spec):
        spec = in_spec
        for m in self.modules:
            spec = infer_module_shape(m, spec)
        return spec

    def _apply(self, params, state, x, training, rng):
        new_state: Dict[str, Any] = {}
        for m in self.modules:
            x = self._child_apply(m, x, training, rng, params, state, new_state)
        return x, new_state


class Identity(AbstractModule):
    """Pass-through (reference: ``$DL/nn/Identity.scala``)."""

    def infer_shape(self, in_spec):
        return in_spec

    def _apply(self, params, state, x, training, rng):
        return x, state


class Echo(AbstractModule):
    """Debug pass-through printing shape at trace time (reference: ``$DL/nn/Echo.scala``)."""

    def infer_shape(self, in_spec):
        return in_spec

    def _apply(self, params, state, x, training, rng):
        shapes = jax.tree_util.tree_map(lambda a: a.shape, x)
        print(f"[{self.name()}] {shapes}")  # lint: disable=BDL002 (trace-time debug layer)
        return x, state
