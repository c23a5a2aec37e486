"""Graph — DAG container (reference: ``$DL/nn/Graph.scala``, ``StaticGraph.scala``,
``$DL/utils/DirectedGraph.scala``).

Reference behavior: users wire nodes with ``layer.inputs(node...)``; ``Graph(input,
output)`` topo-sorts into a ``forwardExecution`` array; StaticGraph pre-schedules
execution; backward graph is generated symmetrically.

TPU-native design: the same ``inputs()`` wiring API builds a static DAG; apply is
a single Python loop over the topo order inside the traced function — XLA sees one
flat computation (the reference's pre-scheduling + DnnGraph compilation both
collapse into the jit trace). The backward graph is ``jax.vjp`` of that trace.
Multi-parent nodes receive a ``Table`` of parent outputs (Torch convention).
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from ..utils.table import T, Table
from .module import AbstractModule, Container, Identity, run_child

_node_ids = itertools.count(1)


class ModuleNode:
    """A vertex wrapping a module instance (reference: Node[AbstractModule])."""

    def __init__(self, module: AbstractModule, parents: Sequence["ModuleNode"] = ()):
        self.id = next(_node_ids)
        self.module = module
        self.parents: List[ModuleNode] = list(parents)
        # reverse edges let analysis.GraphValidator spot wired-but-dangling
        # nodes (forward-reachable from an input, feeding no output)
        self.children: List[ModuleNode] = []
        for p in self.parents:
            p.children.append(self)

    def __repr__(self):
        return f"Node({self.module.name()})"


def Input() -> ModuleNode:
    """Source placeholder node (reference: ``Input()`` in $DL/nn/Input.scala)."""
    return ModuleNode(Identity().set_name(f"Input{next(_node_ids)}"), [])


def _inputs(self: AbstractModule, *parents: ModuleNode) -> ModuleNode:
    """``layer.inputs(n1, n2)`` wiring API (reference: AbstractModule.inputs)."""
    return ModuleNode(self, parents)


AbstractModule.inputs = _inputs  # graft the wiring API onto every module


class Graph(Container):
    def __init__(
        self,
        inputs: Sequence[ModuleNode] | ModuleNode,
        outputs: Sequence[ModuleNode] | ModuleNode,
        validate: bool = True,
    ):
        self.input_nodes = [inputs] if isinstance(inputs, ModuleNode) else list(inputs)
        self.output_nodes = [outputs] if isinstance(outputs, ModuleNode) else list(outputs)
        if validate:
            # fail-fast structural validation (cycles with the offending module
            # names, orphan roots, duplicate names, merge-arity mismatches)
            # BEFORE topo sort / container registration can hit them with a
            # less readable error; ``validate=False`` opts out
            from ..analysis.graph_validator import GraphValidator

            GraphValidator(inputs=self.input_nodes, outputs=self.output_nodes).check()
        self._topo = self._topo_sort()
        # one module at SEVERAL nodes = weight sharing (keras shared layers):
        # register it once — every call site then reads params[name] and the
        # vjp sums gradients across call sites automatically
        seen_ids = set()
        children = []
        for n in self._topo:
            if n in self.input_nodes or id(n.module) in seen_ids:
                continue
            seen_ids.add(id(n.module))
            children.append(n.module)
        super().__init__(*children)

    # -------------------------------------------------------- serialization
    def _serialize_spec(self):
        """DAG topology spec (nodes in topo order + edges by index) for the
        module serializer — the analog of the reference's graph protobuf."""
        from ..utils.module_serializer import module_to_spec

        idx = {node.id: i for i, node in enumerate(self._topo)}
        # shared modules (one module at several nodes = keras weight tying)
        # serialize ONCE and are referenced by index, so sharing survives
        # the round trip instead of silently splitting into copies
        mod_specs: List[Any] = []
        mod_index: Dict[int, int] = {}
        node_mods: List[int] = []
        for n in self._topo:
            key = id(n.module)
            if key not in mod_index:
                mod_index[key] = len(mod_specs)
                mod_specs.append(module_to_spec(n.module))
            node_mods.append(mod_index[key])
        return {
            "class": type(self).__name__,
            "module": type(self).__module__,
            "graph": {
                "modules": mod_specs,
                "nodes": [
                    {
                        "module_index": node_mods[i],
                        "parents": [idx[p.id] for p in n.parents],
                    }
                    for i, n in enumerate(self._topo)
                ],
                "inputs": [idx[n.id] for n in self.input_nodes],
                "outputs": [idx[n.id] for n in self.output_nodes],
            },
        }

    @classmethod
    def _from_spec(cls, spec):
        from ..utils.module_serializer import spec_to_module

        g = spec["graph"]
        modules = [spec_to_module(ms) for ms in g.get("modules", [])]
        built: List[ModuleNode] = []
        for ns in g["nodes"]:  # topo order: parents precede their children
            if "module_index" in ns:
                module = modules[ns["module_index"]]
            else:  # pre-r4 format: per-node inline module spec
                module = spec_to_module(ns["module"])
            built.append(
                ModuleNode(module, [built[i] for i in ns["parents"]])
            )
        return cls([built[i] for i in g["inputs"]], [built[i] for i in g["outputs"]])

    # ------------------------------------------------------------- structure
    def _topo_sort(self) -> List[ModuleNode]:
        # iterative post-order DFS: imported graphs (Caffe/TF) can be deeper
        # than Python's recursion limit
        seen: Dict[int, ModuleNode] = {}
        order: List[ModuleNode] = []
        visiting = set()

        for out in self.output_nodes:
            stack: List[Tuple[ModuleNode, bool]] = [(out, False)]
            while stack:
                node, expanded = stack.pop()
                if node.id in seen:
                    continue
                if expanded:
                    visiting.discard(node.id)
                    seen[node.id] = node
                    order.append(node)
                    continue
                if node.id in visiting:
                    raise ValueError("cycle detected in Graph")
                visiting.add(node.id)
                stack.append((node, True))
                for p in node.parents:
                    if p.id not in seen:
                        stack.append((p, False))
        for inp in self.input_nodes:
            if inp.id not in seen:
                raise ValueError(f"input node {inp} is not connected to any output")
        return order

    def _gather(self, node: ModuleNode, values: Dict[int, object]):
        if len(node.parents) == 1:
            return values[node.parents[0].id]
        return T(*[values[p.id] for p in node.parents])

    # ---------------------------------------------------------------- build
    def build(self, rng, in_spec):
        specs: Dict[int, object] = {}
        graph_inputs = (
            in_spec.to_list() if isinstance(in_spec, Table) else
            list(in_spec) if isinstance(in_spec, (list, tuple)) else [in_spec]
        )
        if len(graph_inputs) != len(self.input_nodes):
            raise ValueError(
                f"Graph expects {len(self.input_nodes)} inputs, got {len(graph_inputs)}"
            )
        for node, spec in zip(self.input_nodes, graph_inputs):
            specs[node.id] = spec
        built_here = set()
        for i, node in enumerate(self._topo):
            if node.id in specs:
                continue
            m = node.module
            if id(m) in built_here:
                # shared module: keep the first call site's parameters; this
                # site only needs its output spec
                specs[node.id] = jax.eval_shape(
                    lambda p, s, xx, m=m: m._apply(p, s, xx, False, None)[0],
                    m.get_parameters(), m.get_state(),
                    self._gather(node, specs),
                )
            else:
                specs[node.id] = m.build(
                    jax.random.fold_in(rng, i), self._gather(node, specs)
                )
                built_here.add(id(m))
        self._built = True
        if len(self.output_nodes) == 1:
            return specs[self.output_nodes[0].id]
        return T(*[specs[n.id] for n in self.output_nodes])

    # ------------------------------------------------------------- contracts
    def infer_shape(self, in_spec, _resolve=None):
        """Spec propagation over the DAG. ``_resolve(node, in_spec)`` is the
        per-node inference hook — analysis.ShapeProp injects its module-path-
        tracking resolver here, so this is the single implementation of the
        graph walk."""
        from .module import infer_module_shape

        resolve = _resolve or (lambda node, spec: infer_module_shape(node.module, spec))
        graph_inputs = (
            in_spec.to_list() if isinstance(in_spec, Table) else
            list(in_spec) if isinstance(in_spec, (list, tuple)) else [in_spec]
        )
        if len(graph_inputs) != len(self.input_nodes):
            raise ValueError(
                f"Graph expects {len(self.input_nodes)} inputs, got {len(graph_inputs)}"
            )
        specs: Dict[int, object] = {}
        for node, spec in zip(self.input_nodes, graph_inputs):
            specs[node.id] = spec
        for node in self._topo:
            if node.id in specs:
                continue
            specs[node.id] = resolve(node, self._gather(node, specs))
        if len(self.output_nodes) == 1:
            return specs[self.output_nodes[0].id]
        return T(*[specs[n.id] for n in self.output_nodes])

    # ---------------------------------------------------------------- apply
    def _apply(self, params, state, x, training, rng):
        values: Dict[int, object] = {}
        graph_inputs = (
            x.to_list() if isinstance(x, Table) else
            list(x) if isinstance(x, (list, tuple)) else [x]
        )
        for node, v in zip(self.input_nodes, graph_inputs):
            values[node.id] = v
        new_state: Dict[str, object] = {}
        for node in self._topo:
            if node.id in values:
                continue
            m = node.module
            y, s = run_child(
                m, params[m.name()], state[m.name()], self._gather(node, values), training, rng
            )
            new_state[m.name()] = s
            values[node.id] = y
        if len(self.output_nodes) == 1:
            return values[self.output_nodes[0].id], new_state
        return T(*[values[n.id] for n in self.output_nodes]), new_state
