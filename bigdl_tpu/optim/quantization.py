"""Low-precision training policy for the flat master-state hot path.

The flat-parameter layout made precision a property you can hang off ONE
vector per state tensor instead of N tree leaves — this module is where it
hangs. That layout lives on the ZeRO-1 sharded ``DistriOptimizer`` step, so
the policy does too. Three knobs, all resolved/validated at optimizer construction
through :func:`bigdl_tpu.utils.compat.resolve_precision_dtype` (so an fp8
request on a stack without float8 dies with a clean ``ValueError``, never an
import crash mid-trace):

* ``comms_dtype`` — wire format of the flat gradient collective, handled by
  :class:`bigdl_tpu.parallel.compression.GradCompressor` (which consumes the
  per-segment scale math defined here).
* ``slot_dtype`` — storage dtype of the flat optimizer slot vectors
  (``"bfloat16"``): carried/donated in bf16, upcast to f32 inside the fused
  ``update_flat``, downcast back with stochastic rounding.
* ``master_dtype`` — storage dtype of the flat master weight vector:
  ``"bfloat16"`` (a plain low-precision master, stochastic-rounded).

Every downcast is stochastically rounded with a key derived from the STEP
COUNTER (``fold_in(base, step)``) — never the host RNG stream, so enabling a
precision policy cannot perturb dropout/shuffle reproducibility, and a
resumed run re-derives the identical rounding decisions from its restored
step counter.

Checkpoints stay in tree layout / f32: the cold seams (checkpoint,
validation, final sync) decode through :meth:`StatePrecision.decode_master`
/ :meth:`decode_slots` before the codec's ``unflatten``, so manifests are
bit-compatible with unquantized runs (quantized↔unquantized resume is
test-locked).

Lint rule BDL013 guards this module (and the comms compressor): no silent
dtype-promoting ops — every ``jnp.zeros``/``arange`` spells its dtype, and
``astype(jnp.float32)`` appears only at the sanctioned dequant seams.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..utils.compat import resolve_precision_dtype

__all__ = [
    "LowPrecisionPolicy", "StatePrecision", "stochastic_round",
    "segment_amax", "scales_from_amax", "quant_range_max",
]

# base PRNG key for stochastic rounding; folded with the step counter (and a
# small per-tensor salt) at trace time. A constant, not host RNG: rounding
# must be a pure function of (value, step).
_SR_BASE_SEED = 0x0B5EED

# largest finite magnitude representable per quantized wire/storage format
_QUANT_RANGE = {
    "int8": 127.0,
    "float8_e4m3fn": 448.0,
    "float8_e5m2": 57344.0,
}

# relative dither half-width for float8 stochastic rounding: one ulp at the
# mantissa width (e4m3: 3 bits, e5m2: 2 bits)
_F8_REL_ULP = {"float8_e4m3fn": 2.0 ** -3, "float8_e5m2": 2.0 ** -2}


def quant_range_max(dtype) -> float:
    """Largest representable magnitude of a supported quantized dtype."""
    name = jnp.dtype(dtype).name
    try:
        return _QUANT_RANGE[name]
    except KeyError:
        raise ValueError(f"no quantization range for dtype {name!r}") from None


def segment_amax(vec: jnp.ndarray, seg_ids, n_segments: int) -> jnp.ndarray:
    """Per-segment max |v| over a flat (slice of a) vector — THE segment-wise
    amax reduction of the low-precision path, riding the same
    ``FlatParameter.segment_ids()`` machinery obs/health's flat reductions
    use. Returns ``(n_segments,)`` f32 (callers pass ``len(fp.sizes) + 1`` so
    the padding tail owns its own — all-zero — row)."""
    return jax.ops.segment_max(
        jnp.abs(vec.astype(jnp.float32)),  # lint: disable=BDL013 amax reduction runs in f32 by contract
        seg_ids,
        num_segments=n_segments,
        indices_are_sorted=True,
    )


def scales_from_amax(amax: jnp.ndarray, qmax: float) -> jnp.ndarray:
    """amax → symmetric quantization scales (1.0 for all-zero segments, so
    0/scale stays 0 and the padding tail never divides by zero)."""
    return jnp.where(amax > 0, amax / qmax, jnp.ones_like(amax))


def stochastic_round(x: jnp.ndarray, dtype, key) -> jnp.ndarray:
    """Stochastically round an f32 vector down to ``dtype``.

    * bf16 — exact SR via the bit trick: add 16 uniform random bits below the
      bf16 mantissa boundary, truncate. Unbiased: E[SR(x)] == x.
    * float8 — dithered rounding: a symmetric ±half-ulp relative perturbation
      before the round-to-nearest cast (f8 is not a bit-prefix of f32, so the
      truncation trick does not apply). Unbiased to first order.
    * f32 — identity (policy off for this tensor).
    """
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float32:
        return x
    if dtype == jnp.dtype(jnp.bfloat16):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        noise = jax.random.bits(key, x.shape, jnp.uint32) & jnp.uint32(0xFFFF)
        rounded = ((bits + noise) >> 16).astype(jnp.uint16)
        return jax.lax.bitcast_convert_type(rounded, jnp.bfloat16)
    name = dtype.name
    if name in _F8_REL_ULP:
        u = jax.random.uniform(key, x.shape, jnp.float32, -0.5, 0.5)
        y = x * (1.0 + u * (2.0 * _F8_REL_ULP[name]))
        # the float8 formats have no inf: a dithered value nudged past the
        # format max would cast to NaN, so saturate explicitly first
        qmax = _QUANT_RANGE[name]
        return jnp.clip(y, -qmax, qmax).astype(dtype)
    raise ValueError(f"stochastic_round: unsupported target dtype {name!r}")


class LowPrecisionPolicy:
    """Resolved + validated low-precision knobs for ONE optimizer instance.

    Built once in ``Optimizer.__init__`` (invalid names and fp8-on-an-
    unsupported-stack fail there, not steps later inside a trace) and kept
    for the optimizer's life, so the step caches can key on plain object
    identity across retry/resume attempts.
    """

    def __init__(self, comms_dtype=None, error_feedback: bool = True,
                 master_dtype=None, slot_dtype=None):
        self.comms_dtype = resolve_precision_dtype(comms_dtype, "comms_dtype")
        self.master_dtype = resolve_precision_dtype(master_dtype, "master_dtype")
        self.slot_dtype = resolve_precision_dtype(slot_dtype, "slot_dtype")
        if self.master_dtype is not None and jnp.dtype(self.master_dtype) not in (
            jnp.dtype(jnp.bfloat16),
        ):
            raise ValueError(
                "master_dtype supports 'bfloat16' (f32 is the default; int8 "
                "master weights have no gradient, and a float8 master would "
                "need per-segment scales gathered beside the ZeRO-1 shards)"
            )
        if self.slot_dtype is not None and jnp.dtype(self.slot_dtype) not in (
            jnp.dtype(jnp.bfloat16),
        ):
            raise ValueError(
                "slot_dtype supports 'bfloat16' (f32 is the default; fp8 "
                "second moments underflow and int8 slots have no update rule)"
            )
        # error feedback is a property of the compressed COMMS path
        self.error_feedback = bool(error_feedback) and self.comms_dtype is not None

    # ------------------------------------------------------------ predicates
    @property
    def active(self) -> bool:
        return (
            self.comms_dtype is not None
            or self.master_dtype is not None
            or self.slot_dtype is not None
        )

    @property
    def quantizes_state(self) -> bool:
        return self.master_dtype is not None or self.slot_dtype is not None

    def describe(self) -> Dict[str, Any]:
        """JSON-able policy summary for telemetry/bench artifacts."""
        name = lambda d: None if d is None else jnp.dtype(d).name  # noqa: E731
        return {
            "comms_dtype": name(self.comms_dtype),
            "error_feedback": self.error_feedback,
            "master_dtype": name(self.master_dtype),
            "slot_dtype": name(self.slot_dtype),
        }


class StatePrecision:
    """``master_dtype``/``slot_dtype`` policy bound to a FlatParameter codec:
    owns the encode (entry commit), decode (cold seams + in-step upcast) and
    the stochastically-rounded per-step downcast around the fused
    ``update_flat``. Everything here is pure jnp — traced straight into the
    jitted step builders."""

    def __init__(self, fp, policy: LowPrecisionPolicy):
        self.fp = fp
        self.policy = policy

    # ------------------------------------------------------- master encoding
    def encode_master(self, vec_f32) -> jnp.ndarray:
        """f32 master vector → stored vector. Runs once per optimize()/resume
        at the entry-commit seam (round to nearest — SR only matters on the
        repeated per-step downcasts)."""
        md = self.policy.master_dtype
        return vec_f32 if md is None else vec_f32.astype(md)

    def decode_master(self, stored) -> jnp.ndarray:
        """Stored master → f32 (the sanctioned master dequant seam)."""
        if self.policy.master_dtype is None:
            return stored
        return stored.astype(jnp.float32)  # lint: disable=BDL013 the sanctioned bf16-master dequant seam

    def downcast_master(self, vec_f32, key) -> jnp.ndarray:
        """Per-step f32 → stored downcast with stochastic rounding."""
        md = self.policy.master_dtype
        return vec_f32 if md is None else stochastic_round(vec_f32, md, key)

    # --------------------------------------------------------- slot encoding
    def _is_flat_slot(self, v) -> bool:
        return getattr(v, "shape", None) == (self.fp.padded_total,)

    def encode_slots(self, slots: Dict[str, Any]) -> Dict[str, Any]:
        """Entry-commit cast of the flat slot vectors to ``slot_dtype``
        (scalar slot state passes through)."""
        sd = self.policy.slot_dtype
        if sd is None:
            return slots
        return {
            k: v.astype(sd) if self._is_flat_slot(v) else v
            for k, v in slots.items()
        }

    def decode_slots(self, slots: Dict[str, Any]) -> Dict[str, Any]:
        """Stored slots → f32 for the fused update / the cold tree-view
        seams. Shard-shaped slot vectors (the ZeRO-1 layout) upcast too —
        anything floating below f32 is a stored low-precision vector."""
        if self.policy.slot_dtype is None:
            return slots
        sd = jnp.dtype(self.policy.slot_dtype)
        return {
            k: v.astype(jnp.float32)  # lint: disable=BDL013 the sanctioned slot dequant seam
            if getattr(v, "dtype", None) == sd
            else v
            for k, v in slots.items()
        }

    def downcast_slots(self, slots: Dict[str, Any], key) -> Dict[str, Any]:
        """Per-step f32 → stored downcast of the updated slot vectors, each
        with its own stochastic-rounding stream (salted by position so two
        slots of equal value round independently)."""
        sd = self.policy.slot_dtype
        if sd is None:
            return slots
        out: Dict[str, Any] = {}
        for i, (k, v) in enumerate(sorted(slots.items())):
            if getattr(v, "dtype", None) == jnp.dtype(
                jnp.float32
            ) and getattr(v, "ndim", 0) == 1:
                out[k] = stochastic_round(v, sd, jax.random.fold_in(key, i))
            else:
                out[k] = v
        return out

    # ------------------------------------------------------------ step seam
    def sr_key(self, step):
        """The stochastic-rounding key for one step: a pure function of the
        step counter (never the host RNG stream — reproducibility and
        resume-identity both depend on this)."""
        return jax.random.fold_in(jax.random.PRNGKey(_SR_BASE_SEED), step)

    def apply_update(self, method, gvec_f32, master_stored, slots_stored,
                     lr, step, *, wd_coeff=None, lr_scale=None,
                     pad_zero=None, p32=None):
        """The policy-wrapped fused update: decode stored state to f32, run
        the method's segment-wise ``update_flat``, stochastically downcast
        the results back to storage precision. ``p32`` short-cuts the master
        decode when the caller already materialized it for the forward.
        Returns ``(stored_master, stored_slots, p32_old, p32_new)`` — the
        f32 views ride out so health statistics see full-precision weights."""
        if p32 is None:
            p32 = self.decode_master(master_stored)
        s32 = self.decode_slots(slots_stored)
        new_p32, new_s32 = method.update_flat(
            gvec_f32, p32, s32, lr, step, wd_coeff=wd_coeff, lr_scale=lr_scale
        )
        if pad_zero is not None:
            # re-zero the inert tail in f32, BEFORE the downcast
            new_p32 = pad_zero(new_p32)
        key = self.sr_key(step)
        stored_p = self.downcast_master(new_p32, jax.random.fold_in(key, 0xA))
        stored_slots = self.downcast_slots(
            new_s32, jax.random.fold_in(key, 0xB)
        )
        return stored_p, stored_slots, p32, new_p32
