"""Ground-distance utilities shared by every EMD approximation.

The paper uses the Euclidean (L2) distance between embedding vectors as the
transportation cost. Cost matrices are built with the stable
``||a-b||^2 = ||a||^2 + ||b||^2 - 2 a.b`` expansion so the heavy term is a
single MXU matmul.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.precision import matmul_precision

Array = jax.Array

#: RELATIVE zero-snap: squared distances below ZERO_SNAP^2 x (|a|^2+|b|^2)
#: collapse to exact 0. The matmul expansion leaves ~eps_f32 x (|a|^2+|b|^2)
#: cancellation residue on IDENTICAL coordinates, which would silently
#: defeat the paper's zero-cost overlap detection (OMR, Theorem 3). Exact
#: zeros are load-bearing here; the threshold scales with the coordinate
#: magnitude because the rounding error does.
ZERO_SNAP = 1e-3


def pairwise_sqdist(a: Array, b: Array) -> Array:
    """Squared Euclidean distances between rows of ``a`` (na,m) and ``b`` (nb,m)."""
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    a2 = jnp.sum(a * a, axis=-1, keepdims=True)          # (na, 1)
    b2 = jnp.sum(b * b, axis=-1, keepdims=True).T        # (1, nb)
    cross = jnp.matmul(a, b.T, precision=matmul_precision(a.dtype))  # MXU
    return jnp.maximum(a2 + b2 - 2.0 * cross, 0.0)


def pairwise_dist(a: Array, b: Array, snap: float = ZERO_SNAP, *,
                  compute_dtype=None) -> Array:
    """Euclidean distances between rows of ``a`` and ``b``; near-zero values
    collapse to exact 0 relative to pair magnitude (see ZERO_SNAP).

    ``compute_dtype`` (a precision policy's compute role) drops only the
    MXU matmul OPERANDS to the reduced dtype — the contraction still
    accumulates into float32 (``preferred_element_type``), and the norm
    terms, snap, and sqrt stay in the input dtype, so the result dtype
    is unchanged. ``None`` (or the input dtype itself) contracts in the
    input dtype. Either way the operand dtype sets the MXU precision
    (:func:`~repro.core.precision.matmul_precision`).
    """
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    a2 = jnp.sum(a * a, axis=-1, keepdims=True)          # (na, 1)
    b2 = jnp.sum(b * b, axis=-1, keepdims=True).T        # (1, nb)
    if compute_dtype is not None and jnp.dtype(compute_dtype) != a.dtype:
        cross = jax.lax.dot_general(
            a.astype(compute_dtype), b.astype(compute_dtype),
            (((1,), (1,)), ((), ())), precision=matmul_precision(compute_dtype),
            preferred_element_type=jnp.float32).astype(a.dtype)
    else:
        cross = jnp.matmul(a, b.T, precision=matmul_precision(a.dtype))
    d2 = jnp.maximum(a2 + b2 - 2.0 * cross, 0.0)
    if snap:
        d2 = jnp.where(d2 < snap * snap * (a2 + b2), 0.0, d2)
    return jnp.sqrt(d2)


def weighted_centroids(w: Array, x: Array) -> Array:
    """Weighted centroids ``sum_s w[..., s] * x[..., s, :]`` of
    histograms: w (..., s), x (..., s, m) -> (..., m), one contraction
    at the operands' precision (``matmul_precision``)."""
    return jnp.einsum("...s,...sm->...m", w, x,
                      precision=matmul_precision(x.dtype))


def l1_normalize(w: Array, axis: int = -1, eps: float = 1e-12) -> Array:
    """L1-normalize nonnegative weights along ``axis`` (histogram convention)."""
    s = jnp.sum(w, axis=axis, keepdims=True)
    return w / jnp.maximum(s, eps)


def l2_normalize(x: Array, axis: int = -1, eps: float = 1e-12) -> Array:
    n = jnp.linalg.norm(x, axis=axis, keepdims=True)
    return x / jnp.maximum(n, eps)
