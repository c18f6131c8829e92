"""Precision policies: the storage/compute/accumulate dtype triple the
batched scoring pipeline threads end to end.

At production corpus sizes the Phase-1 distance table and the handoff
ladders — not FLOPs — cap what fits per device (ROADMAP "Mixed-precision
pipeline"). A :class:`PrecisionPolicy` names the three dtype roles:

* ``storage`` — the Phase-1 handoff arrays (the (nq, v, k) Z/W ladders,
  the (nq, v) masked-min row, the (nq, v, h) reverse distance handoff)
  and the kernel block buffers that hold them. This is the axis that
  halves memory and collective bytes.
* ``compute`` — the stacked distance-matmul operands. bf16 operands on
  the MXU always accumulate into float32 (``preferred_element_type``),
  so dropping compute precision loses input bits, never sum bits.
* ``accum``  — reductions (pours, cumsum ladders, (min,+) contractions)
  and every masking/sentinel write. Always float32: the closed-form LC
  reductions tolerate low-precision STORAGE, not low-precision sums.

Three presets:

=========  =========  =========  =======
name       storage    compute    accum
=========  =========  =========  =======
f32        float32    float32    float32   (default — bitwise unchanged)
bf16       bfloat16   float32    float32
bf16_agg   bfloat16   bfloat16   float32
=========  =========  =========  =======

Sentinel representability (the PR's bugfix): the float32 sentinel
``lc.PAD_DIST`` (1e30) overflows float16 to inf and rounds in bfloat16,
so every reduced-precision path writes :func:`pad_dist_for` (dtype)
instead — finite, exactly representable in that dtype, above any real
transport cost, and guaranteed to upcast to at least the float32
sentinel wherever the dtype's range allows. All sentinel comparisons in
the pipeline are STRICT (``C < pad``), so equality after an exact
upcast round-trip still excludes the sentinel.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

#: ``lc.PAD_DIST`` (1e30) as float32 — it rounds UP to ~1.000000015e30,
#: so it is itself a valid round-up sentinel and the float32 pad value
#: is BITWISE the historical ``jnp.asarray(1e30, float32)``.
_PAD_F32 = float(np.float32(1e30))


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """One storage/compute/accumulate dtype triple (dtype names as
    strings — hashable, so a policy or its name rides through
    ``jax.jit`` static arguments)."""
    name: str
    storage: str
    compute: str
    accum: str


POLICIES = {
    "f32": PrecisionPolicy("f32", "float32", "float32", "float32"),
    "bf16": PrecisionPolicy("bf16", "bfloat16", "float32", "float32"),
    "bf16_agg": PrecisionPolicy("bf16_agg", "bfloat16", "bfloat16",
                                "float32"),
}


def resolve(precision) -> PrecisionPolicy:
    """Preset name (or an already-resolved policy) -> PrecisionPolicy."""
    if isinstance(precision, PrecisionPolicy):
        return precision
    if precision in POLICIES:
        return POLICIES[precision]
    raise ValueError(f"unknown precision policy {precision!r}; "
                     f"one of {sorted(POLICIES)}")


def matmul_precision(dtype) -> jax.lax.Precision:
    """MXU precision of a contraction over ``dtype`` operands — the one
    place the pipeline decides it. Float32 operands take HIGHEST, the
    float32 algorithm: XLA's DEFAULT on a TPU rounds them to a single
    bfloat16 pass, which moves near-tied distances away from the float32
    reference. Narrower operands take DEFAULT, their one native pass
    (bf16 x bf16 products are exact in the float32 accumulator, and
    Mosaic refuses a float32 contraction on bfloat16 operands)."""
    if jnp.dtype(dtype).itemsize >= 4:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


@functools.lru_cache(maxsize=None)
def _pad_dist_cached(name: str) -> float:
    dt = jnp.dtype(name)
    if dt.itemsize >= 4:
        return _PAD_F32
    fi = jnp.finfo(dt)
    # Narrow-range dtypes (float16: max 65504) cap the sentinel well
    # below the float32 one — but still orders of magnitude above any
    # real transport cost, and finite so 0-mass remainders cost 0.
    target = min(_PAD_F32, float(fi.max) / 8.0)
    x = dt.type(target)
    # Round UP to the first representable value whose upcast clears the
    # target (nearest-rounding may have landed below it).
    while float(x) < target:
        x = dt.type(float(x) * (1.0 + float(fi.eps)))
    return float(x)


def pad_dist_for(dtype) -> float:
    """The padding-distance sentinel for ``dtype``, as a Python float.

    Finite, below ``finfo(dtype).max``, above any real transport cost,
    exactly representable in ``dtype`` (so a downcast-then-upcast
    round-trip is exact), and — for every dtype whose range reaches it —
    at least the float32 sentinel on upcast, keeping strict ``< pad``
    comparisons correct across mixed-precision handoffs.
    ``pad_dist_for(float32)`` is bitwise the historical ``lc.PAD_DIST``.
    """
    return _pad_dist_cached(jnp.dtype(dtype).name)
