"""Gradient checkpointing (rematerialization) as a module wrapper.

TPU-native HBM lever (no reference analog — the reference's executors keep
every activation; on TPU the usual bottleneck is HBM, and ``jax.checkpoint``
trades FLOPs for memory by recomputing a subtree's activations during the
backward pass instead of storing them). Wrapping is zero-math-change:
outputs and gradients are bit-identical to the unwrapped module; only the
autodiff schedule differs.

Typical use — checkpoint each big block so peak activation memory scales
with ONE block instead of the whole depth::

    nn.Sequential(*[nn.Remat(make_block()) for _ in range(n_layers)])

``policy`` selects what XLA may still save (names from
``jax.checkpoint_policies``, e.g. ``'dots_saveable'`` keeps MXU outputs).

The default (``policy=None``) rematerializes everything EXCEPT what a kernel
inside the block marked as dear to recompute and cheap to keep
(``utils/remat_keep.keep``; the names are ``KEPT_NAMES``). Today that is the
flash-attention kernel's output and per-row logsumexp: the kernel's own
backward needs exactly those two, and without them the backward would run the
whole forward kernel a second time only to rebuild them. Keeping them costs
one activation in the compute dtype and one float32 row statistic per
attention call and block (134 MB + 2 MB at 2 x 32 heads x 8192 x 128 in bf16);
it saves one ``flash_fwd`` per block and step (33 ms of Mellum2's 581 ms
device step, PERF.md section 6, PR 31). A block that marks nothing saves nothing: the
program is what ``jax.checkpoint`` with no policy traces. What a compiled step
kept is in its telemetry ``compile`` record (``remat_kept``).
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax

from ..utils.remat_keep import KEPT_NAMES, keeping_block
from .module import Container, AbstractModule, run_child

# zero-argument policies only: the other jax.checkpoint_policies attributes
# are combinators/factories (save_only_these_names, save_from_both_policies,
# ...) that take arguments — passing one raw to jax.checkpoint fails late or
# silently saves everything
_POLICIES = (
    "everything_saveable",
    "nothing_saveable",
    "dots_saveable",
    "checkpoint_dots",
    "dots_with_no_batch_dims_saveable",
    "checkpoint_dots_with_no_batch_dims",
)


class Remat(Container):
    """Wrap one module so its backward rematerializes instead of storing.

    Args:
        module: the wrapped subtree.
        policy: optional ``jax.checkpoint_policies`` attribute name
            (string, serializable), e.g. ``'dots_saveable'``,
            ``'nothing_saveable'``, ``'everything_saveable'``.
    """

    def __init__(self, module: AbstractModule, policy: Optional[str] = None):
        if policy is not None and policy not in _POLICIES:
            raise ValueError(
                f"unknown checkpoint policy {policy!r}; one of {_POLICIES} "
                "(argument-taking jax.checkpoint_policies combinators are "
                "not expressible here)")
        super().__init__(module)
        self.policy = policy

    def add(self, module: AbstractModule) -> "Remat":
        if getattr(self, "modules", None):
            raise ValueError(
                "Remat wraps exactly ONE module; wrap a Sequential to "
                "checkpoint several layers together")
        return super().add(module)

    def build(self, rng, in_spec):
        out = self.modules[0].build(rng, in_spec)
        self._built = True
        return out

    def infer_shape(self, in_spec):
        # checkpointing is a schedule change, not a math change: the contract
        # is exactly the wrapped module's
        from .module import infer_module_shape

        return infer_module_shape(self.modules[0], in_spec)

    def _apply(self, params, state, x, training, rng):
        child = self.modules[0]
        if self.policy is None:
            # a subtree that marks nothing saves nothing, as with no policy
            policy = jax.checkpoint_policies.save_only_these_names(
                *KEPT_NAMES)
            recording = keeping_block()
        else:
            policy = getattr(jax.checkpoint_policies, self.policy)
            recording = contextlib.nullcontext()
        inner = jax.checkpoint(
            lambda p, s, xx, r: run_child(child, p, s, xx, training, r),
            policy=policy)
        with recording:
            y, ns = inner(params[child.name()], state[child.name()], x, rng)
        return y, {child.name(): ns}
