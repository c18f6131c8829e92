"""Pallas TPU kernel: fused pairwise-distance + running row-top-k (Phase 1).

The paper materializes the v x h distance matrix D on the GPU and then
reduces it. On TPU we tile V (over the grid's parallel axis) and Q (over an
arbitrary-order reduction axis), compute each (bv, bh) distance tile on the
MXU via the ||a-b||^2 = ||a||^2 + ||b||^2 - 2ab expansion, and merge the
tile's k smallest entries per row into a running (Z, S) carried in the
output refs — D never leaves VMEM. Output is O(v*k) instead of O(v*h).

k is small (<= 16 in the paper), so selection is a k-round masked row-min
network on the VPU rather than a sort: each round extracts the current row
minimum and masks it out with a one-hot built from broadcasted iota.

The grid carries a query-batch dimension as its outermost (parallel) axis:
a batch of nq queries runs as one kernel launch with coords tiles shared
across queries, so multi-query serving needs no host-side looping.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.precision import matmul_precision, pad_dist_for
from repro.kernels.tiling import compiler_params

BIG = 1e30  # plain float: jnp scalars would be captured consts in the kernel


def _rowmin_extract(d, col_ids, big=BIG):
    """One selection round: per-row (min value, argmin col id), then mask.

    d: (bv, bh) working distances; col_ids: (bv, bh) global column ids.
    Returns (minval (bv,1), minidx (bv,1), d with the winner masked to
    ``big``).
    """
    minval = jnp.min(d, axis=1, keepdims=True)                    # (bv, 1)
    is_min = d == minval
    # Lowest column id among ties — matches lax.top_k tie-breaking.
    idx_cand = jnp.where(is_min, col_ids, jnp.int32(2**31 - 1))
    minidx = jnp.min(idx_cand, axis=1, keepdims=True)             # (bv, 1)
    d = jnp.where(col_ids == minidx, big, d)
    return minval, minidx, d


def _dist_topk_kernel(v_ref, q_ref, qmask_ref, z_ref, s_ref, *, k: int,
                      block_h: int, out_dtype):
    """Grid = (nq, v_blocks, h_blocks); the query batch is the outermost
    (parallel) axis, h the innermost sequential merge axis. Each (q, i)
    output block carries its running (Z, S) across the h sweep."""
    j = pl.program_id(2)
    # Sentinel exactly representable in the OUTPUT dtype: masked entries
    # survive the f32 -> out_dtype store bit-exactly, so downstream strict
    # ``< pad`` comparisons still exclude them (pad_dist_for(float32) is
    # bitwise the historical BIG). All selection work stays float32.
    big = pad_dist_for(out_dtype)

    vc, qc = v_ref[...], q_ref[0]          # compute dtype: (bv, m), (bh, m)
    vt, qt = vc.astype(jnp.float32), qc.astype(jnp.float32)
    v2 = jnp.sum(vt * vt, axis=1, keepdims=True)                  # (bv, 1)
    q2 = jnp.sum(qt * qt, axis=1, keepdims=True).T                # (1, bh)
    # the cross term contracts the compute-dtype operands at their
    # precision (float32: HIGHEST, as the jnp engines) into float32
    d = v2 + q2 - 2.0 * jax.lax.dot_general(
        vc, qc, (((1,), (1,)), ((), ())),
        precision=matmul_precision(vc.dtype),
        preferred_element_type=jnp.float32)                       # (bv, bh)
    d = jnp.maximum(d, 0.0)
    # relative ZERO_SNAP (see core/geometry.py): exact zeros are load-bearing
    d = jnp.where(d < 1e-6 * (v2 + q2), 0.0, d)
    d = jnp.sqrt(d)
    # Invalid columns (padding / zero-weight query bins) never win.
    d = jnp.where(qmask_ref[0] > 0, d, big)                       # (1, bh) bcast

    bv = d.shape[0]
    col0 = j * block_h
    col_ids = col0 + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)

    # Tile-local top-k via k min-extraction rounds.
    zs, ss = [], []
    for _ in range(k):
        mv, mi, d = _rowmin_extract(d, col_ids, big)
        zs.append(mv)
        ss.append(mi)
    z_tile = jnp.concatenate(zs, axis=1)                          # (bv, k)
    s_tile = jnp.concatenate(ss, axis=1)                          # (bv, k)

    @pl.when(j == 0)
    def _init():
        z_ref[...] = z_tile[None].astype(out_dtype)
        s_ref[...] = s_tile[None]

    @pl.when(j > 0)
    def _merge():
        # Merge running (k) with tile (k): k extraction rounds over 2k
        # cands. The running Z re-enters the f32 accumulator first —
        # winner masking never happens in the storage dtype.
        zc = jnp.concatenate([z_ref[0].astype(jnp.float32), z_tile],
                             axis=1)                              # (bv, 2k)
        sc = jnp.concatenate([s_ref[0], s_tile], axis=1)
        out_z, out_s = [], []
        work = zc
        for _ in range(k):
            mv = jnp.min(work, axis=1, keepdims=True)
            is_min = work == mv
            cand = jnp.where(is_min, sc, jnp.int32(2**31 - 1))
            mi = jnp.min(cand, axis=1, keepdims=True)
            # Mask exactly one winner slot (first matching position).
            pos = jax.lax.broadcasted_iota(jnp.int32, work.shape, 1)
            win_pos = jnp.min(jnp.where(is_min & (sc == mi), pos,
                                        jnp.int32(2**31 - 1)),
                              axis=1, keepdims=True)
            work = jnp.where(pos == win_pos, big, work)
            out_z.append(mv)
            out_s.append(mi)
        z_ref[...] = jnp.concatenate(out_z, axis=1)[None].astype(out_dtype)
        s_ref[...] = jnp.concatenate(out_s, axis=1)[None]


@functools.partial(jax.jit,
                   static_argnames=("k", "block_v", "block_h", "interpret",
                                    "out_dtype"))
def dist_topk_pallas(coords: jax.Array, qc: jax.Array, qmask: jax.Array,
                     k: int, *, block_v: int = 256, block_h: int = 256,
                     interpret: bool = False, out_dtype: str = "float32"):
    """Fused Euclidean distance + row-top-k over a query batch.

    Args:
      coords: (v, m) vocabulary embedding vectors, shared by all queries.
      qc:     (nq, h, m) query-bin embedding vectors. Both in the
        precision policy's compute dtype (same for both), which sets the
        distance matmul's precision; norms and selection are float32.
      qmask:  (nq, 1, h) 1.0 for valid query bins, 0.0 for padding.
      k:      number of smallest distances to keep per vocabulary row.
      out_dtype: storage dtype of Z (a precision policy's storage role);
        selection always runs in float32 with a sentinel representable in
        ``out_dtype`` (see ``_dist_topk_kernel``).
    Returns:
      Z: (nq, v, k) ascending distances in ``out_dtype``;
      S: (nq, v, k) int32 bin indices.
    Caller guarantees v % block_v == 0 and h % block_h == 0 (see ops.py).
    """
    v, m = coords.shape
    nq, h, _ = qc.shape
    assert v % block_v == 0 and h % block_h == 0, (v, h, block_v, block_h)
    grid = (nq, v // block_v, h // block_h)
    kernel = functools.partial(_dist_topk_kernel, k=k, block_h=block_h,
                               out_dtype=jnp.dtype(out_dtype))
    z, s = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_v, m), lambda q, i, j: (i, 0)),
            pl.BlockSpec((1, block_h, m), lambda q, i, j: (q, j, 0)),
            pl.BlockSpec((1, 1, block_h), lambda q, i, j: (q, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_v, k), lambda q, i, j: (q, i, 0)),
            pl.BlockSpec((1, block_v, k), lambda q, i, j: (q, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, v, k), jnp.dtype(out_dtype)),
            jax.ShapeDtypeStruct((nq, v, k), jnp.int32),
        ],
        compiler_params=compiler_params("parallel", "parallel",
                                        "arbitrary"),
        interpret=interpret,
    )(coords, qc, qmask)
    return z, s
