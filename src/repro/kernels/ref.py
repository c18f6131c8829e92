"""Pure-jnp oracles for the Pallas kernels (allclose targets in tests).

The ``cand_*`` oracles mirror the candidate kernels with an XLA gather in
place of the in-kernel one-hot gather — the reduction formulas are the
reference engines' own (``lc.pour`` / ``lc.ict_pour`` / the Algorithm-1
and masked-min expressions), so the fused kernels are expected to match
them exactly, not just within tolerance (``tests/test_cand_kernels.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.geometry import pairwise_dist
from repro.core.lc import PAD_DIST, gather_per_query, ict_pour, pour


def dist_topk_ref(coords: jax.Array, qc: jax.Array, qmask: jax.Array, k: int):
    """Materialized-D reference for ``dist_topk``: full (v, h) distance matrix
    then lax.top_k of the negated rows."""
    D = pairwise_dist(coords.astype(jnp.float32), qc.astype(jnp.float32))
    D = jnp.where(qmask.reshape(1, -1) > 0, D, PAD_DIST)
    neg, s = jax.lax.top_k(-D, k)
    return -neg, s


def dist_topk_batched_ref(coords: jax.Array, qcs: jax.Array,
                          qmask: jax.Array, k: int):
    """Per-query loop of ``dist_topk_ref``: the (nq, v, k) oracle for the
    query-batched kernel grid."""
    return jax.vmap(lambda qc, qm: dist_topk_ref(coords, qc, qm, k))(
        qcs, qmask)


def act_phase2_ref(x: jax.Array, zg: jax.Array, wg: jax.Array) -> jax.Array:
    """Sequential-rounds reference for ``act_phase2`` — implements the
    paper's eqs. (6)-(9) literally: k-1 min/subtract rounds then the dump."""
    x = x.astype(jnp.float32)
    iters = wg.shape[-1]
    t = jnp.zeros(x.shape[:-1], jnp.float32)
    for l in range(iters):
        y = jnp.minimum(x, wg[..., l].astype(jnp.float32))   # eq. (6)
        x = x - y                                            # eq. (7)
        t = t + jnp.sum(y * zg[..., l], axis=-1)             # eq. (8)
    t = t + jnp.sum(x * zg[..., iters], axis=-1)             # eq. (9)
    return t[..., None]


def act_phase2_batched_ref(x: jax.Array, zg: jax.Array,
                           wg: jax.Array) -> jax.Array:
    """Per-query loop of ``act_phase2_ref`` over shared x: the (nq, n)
    oracle for the query-batched pour grid."""
    return jax.vmap(lambda z, w: act_phase2_ref(x, z, w)[:, 0])(zg, wg)


def cand_pour_ref(idsg: jax.Array, xg: jax.Array, Z: jax.Array,
                  W: jax.Array | None, iters: int) -> jax.Array:
    """XLA-gather oracle for ``cand_pour``: per-query ladder gather at the
    candidate entries, then the reference ``lc.pour``."""
    Zg = gather_per_query(Z[..., :iters + 1], idsg)
    if iters == 0:
        return jnp.sum(xg * Zg[..., 0], axis=-1)
    Wg = gather_per_query(W[..., :iters], idsg)
    return pour(xg, Zg, Wg, iters)


def cand_omr_ref(idsg: jax.Array, xg: jax.Array, Z: jax.Array,
                 W0: jax.Array) -> jax.Array:
    """XLA-gather oracle for ``cand_omr`` (Algorithm-1 top-2 reduction)."""
    Zg = gather_per_query(Z[..., :2], idsg)
    W0g = gather_per_query(W0, idsg)
    overlap = Zg[..., 0] == 0.0
    rest = xg - jnp.minimum(xg, W0g)
    per_entry = jnp.where(overlap, rest * Zg[..., 1], xg * Zg[..., 0])
    return jnp.sum(per_entry, axis=-1)


def cand_rev_min_ref(idsg: jax.Array, xg: jax.Array, Dq: jax.Array,
                     qw: jax.Array) -> jax.Array:
    """XLA-gather oracle for ``cand_rev_min`` (masked (min,+) . q_w)."""
    Dg = gather_per_query(Dq, idsg)                      # (nq, b, hmax, h)
    Dg = jnp.where((xg > 0.0)[..., None], Dg, jnp.asarray(PAD_DIST,
                                                          Dg.dtype))
    cmin = jnp.min(Dg, axis=2)                           # (nq, b, h)
    return jnp.sum(cmin * qw[:, None, :], axis=-1)


def cand_ict_ref(idsg: jax.Array, xg: jax.Array, Dq: jax.Array,
                 qw: jax.Array) -> jax.Array:
    """XLA-gather oracle for ``cand_ict`` (full-ladder Algorithm-2 pour,
    max-FINITE remainder dump via ``lc.ict_pour``)."""
    C = gather_per_query(Dq, idsg)                       # (nq, b, hmax, h)
    cap = jnp.broadcast_to(qw[:, None, None, :], C.shape)
    return ict_pour(xg, cap, C)
