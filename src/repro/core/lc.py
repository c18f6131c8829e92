"""Linear-complexity batch engines: LC-RWMD, LC-OMR, LC-ACT (Section 5).

One query histogram is scored against ``n`` database histograms that share a
vocabulary ``V`` of ``v`` coordinates in R^m. Per-query work against the
vocabulary is done ONCE (Phase 1), then reused across all database rows
(Phases 2/3). The ``*_batched`` engines lift that amortization one level
further: a whole query batch shares one stacked Phase-1 matmul and a
query-blocked Phase-2 schedule (see the "Batched multi-query engines"
section below). Single-query structure:

  Phase 1:  D = dist(V, Qcoords)            (v, h)   -- one MXU matmul
            Z, S = row-top-k smallest of D  (v, k)
            W[i, l] = q_w[S[i, l]]          (v, k)   -- capacities
  Phase 2:  k-1 rounds of Y = min(X, w_l); X -= Y; t += Y . z_l
  Phase 3:  t += X . z_k                    (dump remainder)

TPU adaptation (DESIGN.md section 2): the database is stored in a padded
dense-bucket layout (ids, weights) instead of CSR, and Phase 2 gathers the
per-entry (cost, capacity) ladders Zg/Wg once and then runs a fused
element-wise pour — the v x h distance matrix of Phase 1 and the n x v
dense X of the paper never hit HBM at production sizes (see
``kernels/dist_topk`` and ``kernels/act_phase2`` for the fused versions;
this module is the readable pjit-able reference engine that the kernels are
validated against).

The single-device kernel pour of the batched engine can read a second,
segmented copy of the rows instead (:class:`Segments`, built once by
``EmdIndex.build``): only the lane-wide segments that hold real bins, so
its ladder gather skips the padding slots, which pour exactly 0. The
reference branch, the mesh path and every other consumer gather from the
padded layout.

NOTE (serving callers): prefer ``repro.api.EmdIndex`` — these engines are
the thin compute layer behind its ``backend="reference"``/``"pallas"``
paths; calling them directly bypasses batching, symmetric scoring, and
backend selection.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scopes
from repro.core.geometry import pairwise_dist
from repro.core.precision import (matmul_precision, pad_dist_for,
                                  resolve as resolve_precision)
from repro.sharding import annotate

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Corpus:
    """Padded dense-bucket histogram database over a shared vocabulary.

    ids: (n, hmax) int32 vocabulary indices; padding slots carry weight 0.
    w:   (n, hmax) float32 L1-normalized weights (padding = 0).
    coords: (v, m) float32 vocabulary embedding vectors.
    """
    ids: Array
    w: Array
    coords: Array

    @property
    def n(self) -> int:
        return self.ids.shape[0]

    @property
    def hmax(self) -> int:
        return self.ids.shape[1]

    @property
    def v(self) -> int:
        return self.coords.shape[0]

    @property
    def m(self) -> int:
        return self.coords.shape[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Segments:
    """Segmented row layout that the kernel pour's ladder gather reads.

    Each row's real bins (weight > 0), in their order in the row, are cut
    into segments of ``width`` slots; only those segments are stored, so
    the gather skips the padding the dense-bucket layout carries.

    ids:  (S, width) int32 vocabulary indices; unused slots carry id 0.
    w:    (S, width) float32 weights (unused slots and padding segments
          weigh 0). Rows keep their order and each row's segments are
          contiguous; S is padded with zero segments to the pour's row
          block, so the kernel launch pads nothing.
    rows: (n, c) int32 segment indices of each row, c = the most segments
          any row has; an entry of S (out of range) marks no segment.
    """
    ids: Array
    w: Array
    rows: Array

    @property
    def width(self) -> int:
        return self.ids.shape[1]


def segment_rows(corpus: Corpus, block_n: int) -> tuple[Segments, float]:
    """Derive the :class:`Segments` layout of ``corpus`` on the host.

    The segment width is the TPU lane width, or the row width rounded to
    a sublane where rows are narrower: ``min(128, round_up(hmax, 8))``.
    A row of L real bins takes ceil(L / width) segments and a row with
    none takes none. ``block_n`` is the pour kernel's row tile.

    Returns the layout and its fill: real bins over gathered slots, in %.
    """
    from repro.kernels.tiling import LANE, SUBLANE, round_up

    ids, w = np.asarray(corpus.ids), np.asarray(corpus.w)
    n, hmax = ids.shape
    width = min(LANE, round_up(hmax, SUBLANE))
    r, j = np.nonzero(w > 0.0)           # row-major: each row's bins in order
    lengths = np.bincount(r, minlength=n)
    counts = -(-lengths // width)
    s_real = int(counts.sum())
    block = min(block_n, round_up(max(s_real, 1), SUBLANE))
    s = round_up(max(s_real, 1), block)
    first = np.cumsum(counts) - counts
    p = np.arange(r.size) - (np.cumsum(lengths) - lengths)[r]  # place in row
    seg, lane = first[r] + p // width, p % width
    seg_ids = np.zeros((s, width), ids.dtype)
    seg_w = np.zeros((s, width), w.dtype)
    seg_ids[seg, lane] = ids[r, j]
    seg_w[seg, lane] = w[r, j]
    k = np.arange(max(int(counts.max(initial=0)), 1))
    rows = np.where(k < counts[:, None], first[:, None] + k, s)
    fill = 100.0 * r.size / (s * width)
    return Segments(ids=jnp.asarray(seg_ids), w=jnp.asarray(seg_w),
                    rows=jnp.asarray(rows, jnp.int32)), fill


#: Finite sentinel for padding query slots. Large enough never to be chosen
#: over a real bin, finite so 0-mass remainders cost 0.0 (inf would NaN).
#: This is the float32 value; reduced-precision arrays must use
#: ``pad_dist_for(dtype)`` instead (1e30 overflows float16 to inf and
#: rounds in bfloat16 — the sentinel must be exactly representable so a
#: downcast/upcast round-trip stays a sentinel). ``pad_dist_for(float32)``
#: is bitwise this constant.
PAD_DIST = 1e30


def _accum(x: Array) -> Array:
    """Upcast a reduced-precision handoff block to the float32
    accumulator dtype. All reductions and sentinel writes run on the
    result, never in bfloat16 storage. A no-op for float32 inputs, so
    the default policy's graph is unchanged bit for bit."""
    return x.astype(jnp.promote_types(x.dtype, jnp.float32))


def _pad_const(dtype):
    """The :func:`pad_dist_for` sentinel as a 0-d array of ``dtype``."""
    return jnp.asarray(pad_dist_for(dtype), dtype)


def mask_pad_rows(scores: Array, n_valid: int | None) -> Array:
    """Push score columns of pad rows (index >= ``n_valid``) to PAD_DIST.

    Zero-weight pad rows score 0 for the LC methods — the best possible
    score — so every top-k consumer (distributed search, cascade
    top-budget) must mask them FIRST. The single home of that invariant.
    """
    if n_valid is None or n_valid >= scores.shape[-1]:
        return scores
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
    return jnp.where(col < n_valid, scores, _pad_const(scores.dtype))


_INT_MAX = jnp.int32(2**31 - 1)


def _extract_smallest_k(work: Array, col_ids: Array, k: int):
    """k rounds of masked min-extraction over the last axis: per row the
    (value, global column id) of the k smallest entries, ascending, ties
    to the lowest id. Extracted entries are masked to PAD_DIST, matching
    the historical ``smallest_k`` semantics on degenerate rows."""
    zs, ss = [], []
    for _ in range(k):
        mv = jnp.min(work, axis=-1, keepdims=True)
        cand = jnp.where(work == mv, col_ids, _INT_MAX)
        mi = jnp.min(cand, axis=-1, keepdims=True)
        work = jnp.where(col_ids == mi, _pad_const(work.dtype), work)
        zs.append(mv)
        ss.append(mi)
    return (jnp.concatenate(zs, axis=-1),
            jnp.concatenate(ss, axis=-1).astype(jnp.int32))


def _merge_smallest_k(zr: Array, sr: Array, zt: Array, st: Array, k: int):
    """Merge running (value, index) registers with a tile's top-k: k
    extraction rounds over the 2k candidates, masking exactly one winner
    position per round (indices may legitimately repeat on degenerate
    rows, so masking by id alone would drop candidates)."""
    zc = jnp.concatenate([zr, zt], axis=-1)              # (..., 2k)
    sc = jnp.concatenate([sr, st], axis=-1)
    pos = jax.lax.broadcasted_iota(jnp.int32, zc.shape, zc.ndim - 1)
    out_z, out_s = [], []
    work = zc
    for _ in range(k):
        mv = jnp.min(work, axis=-1, keepdims=True)
        is_min = work == mv
        mi = jnp.min(jnp.where(is_min, sc, _INT_MAX), axis=-1, keepdims=True)
        win = jnp.min(jnp.where(is_min & (sc == mi), pos, _INT_MAX),
                      axis=-1, keepdims=True)
        work = jnp.where(pos == win, _pad_const(work.dtype), work)
        out_z.append(mv)
        out_s.append(mi)
    return (jnp.concatenate(out_z, axis=-1),
            jnp.concatenate(out_s, axis=-1).astype(jnp.int32))


def smallest_k(D: Array, k: int):
    """Row-wise k smallest (values, indices), ascending, via k rounds of
    masked min-extraction — identical selection to ``lax.top_k`` (lowest
    index wins ties) but built from min/where/iota only, so XLA's SPMD
    partitioner shards it on batch dims. The TopK custom-call does NOT
    partition and forces a full all-gather of D (EXPERIMENTS.md section
    Perf, emd-20news iteration 2). k is small (<= 16) per the paper.

    Each extraction round re-scans the full matrix, so D is read k times;
    ``streaming_smallest_k`` performs the same selection reading D once
    and is what the engines use. This version is kept as the reference
    the streaming path is property-tested against.
    """
    col = jax.lax.broadcasted_iota(jnp.int32, D.shape, D.ndim - 1)
    return _extract_smallest_k(D, col, k)


def streaming_smallest_k(D: Array, k: int, chunk: int = 512):
    """Row-wise k smallest (values, indices) along the last axis in a
    SINGLE pass over ``D``: the columns stream through in tiles of
    ``chunk`` and k running (value, index) registers per row are updated
    by an insertion-compare merge with each tile's candidates — D is read
    once instead of k times (``smallest_k`` re-scans the full matrix per
    extraction round, which at production column counts means k trips to
    HBM). Selection is identical to ``smallest_k`` (ascending values;
    ties resolve to the lowest column index) whenever every row has at
    least k columns; when the column count fits one tile the schedule
    degenerates to a single in-register extraction with no merge.
    """
    h = D.shape[-1]
    if h <= chunk:
        return smallest_k(D, k)
    nchunks = -(-h // chunk)
    # Pad with the sentinel at column ids >= h: real columns win all ties.
    Dp = jnp.pad(D, ((0, 0),) * (D.ndim - 1) + ((0, nchunks * chunk - h),),
                 constant_values=pad_dist_for(D.dtype))
    Dt = jnp.moveaxis(Dp.reshape(D.shape[:-1] + (nchunks, chunk)), -2, 0)
    tile_col = jax.lax.broadcasted_iota(jnp.int32, Dt.shape[1:], D.ndim - 1)
    Z0, S0 = _extract_smallest_k(Dt[0], tile_col, k)

    def body(i, carry):
        d = jax.lax.dynamic_index_in_dim(Dt, i, 0, keepdims=False)
        zt, st = _extract_smallest_k(d, i * chunk + tile_col, k)
        return _merge_smallest_k(*carry, zt, st, k)

    return jax.lax.fori_loop(1, nchunks, body, (Z0, S0))


def take_bins(q_w: Array, S: Array) -> Array:
    """``q_w[S]`` along the last axis — each query's capacities at its
    selected bins: q_w (..., h), S (..., v, k) -> (..., v, k). An exact
    one-hot sum over the h bins (one nonzero per entry) instead of a
    gather: the TPU compiler spends minutes on a gather of v*k indices
    from a length-h row at 20News widths, and seconds on this."""
    col = jax.lax.broadcasted_iota(jnp.int32, (q_w.shape[-1],), 0)
    hit = S[..., None] == col                            # (..., v, k, h)
    return jnp.sum(jnp.where(hit, q_w[..., None, None, :], 0), axis=-1)


@scopes.scoped(scopes.PHASE1)
def phase1(coords: Array, q_ids: Array, q_w: Array, k: int):
    """Phase 1: fused distance + row-top-k against the query.

    Padding query slots (weight 0) are pushed to PAD_DIST so they are never
    selected as a nearest destination. Returns Z (v, k) ascending distances,
    W (v, k) matching query capacities.
    """
    qc = coords[q_ids]                                   # (h, m)
    D = pairwise_dist(coords, qc)                        # (v, h)
    D = jnp.where(q_w[None, :] > 0.0, D, pad_dist_for(D.dtype))
    Z, S = streaming_smallest_k(D, k)                    # (v, k)
    return Z, take_bins(q_w, S)


#: Dedup the Phase-1 column stack only when it exceeds the vocabulary by
#: this factor. Unique-bin stacking trades the stacked matmul's FLOPs
#: (cut by the dedup ratio) for a sort + an extra (v, nq*h) gather, so it
#: pays off on matmul-bound hardware (TPU MXU) at high duplication —
#: corpus-as-queries all-pairs batches — but NOT on small serving batches
#: (and on gather-bound CPU it is roughly a wash even at 16x; see
#: BENCH_batch.json notes).
DEDUP_STACK_RATIO = 4


def stack_query_bins(coords: Array, Q_ids: Array):
    """Phase-1 column stacking with duplicate-bin dedup.

    Stacks every query histogram's bins into one (cols, m) coordinate
    matrix for the single Phase-1 matmul. When the stack far exceeds the
    vocabulary (corpus-as-queries all-pairs batches:
    nq*h >= DEDUP_STACK_RATIO * v), the same vocabulary id appears in
    many histograms and re-embedding it per slot wastes Phase-1 FLOPs —
    so the distinct ids are computed once (``jnp.unique`` with static
    size v, the hard upper bound) and a (nq*h,) inverse map re-expands
    the deduped columns after the matmul. Returns (qc, inv) where
    ``inv`` is None on the no-dedup path.
    """
    nq, h = Q_ids.shape
    flat = Q_ids.reshape(-1)
    v = coords.shape[0]
    if nq * h < DEDUP_STACK_RATIO * v:
        return coords[flat], None
    uniq, inv = jnp.unique(flat, size=v, fill_value=0, return_inverse=True)
    return coords[uniq], inv.reshape(-1)


@scopes.scoped(scopes.PHASE1)
def phase1_stacked_dist(coords: Array, Q_ids: Array, Q_w: Array,
                        precision: str = "f32") -> Array:
    """Stacked Phase-1 distance tensor for the WHOLE query batch: one
    (v, nq*h) matmul (one MXU call instead of nq), reshaped query-major to
    (v, nq, h). Padding query slots (weight 0) are masked to the padding
    sentinel so they are never selected as a nearest destination (finite,
    so 0-mass remainders still cost 0). Mesh-aware: the tensor is pinned
    vocabulary-over-"model" / queries-over-DP
    (``annotate.emd_stacked_dist``; no-op outside a mesh), so the same
    code serves the single-host batched engines and the distributed step.

    ``precision`` (a ``core.precision`` policy name): the matmul operands
    run in the policy's compute dtype (f32 accumulation either way), the
    sentinel mask is applied in float32 with the STORAGE dtype's exactly
    representable sentinel, and the returned tensor is downcast to the
    storage dtype — halving the handoff bytes under the bf16 policies.
    The default leaves the float32 graph bitwise unchanged.
    """
    policy = resolve_precision(precision)
    nq, h = Q_ids.shape
    v = coords.shape[0]
    qc, inv = stack_query_bins(coords, Q_ids)
    compute = None if policy.compute == "float32" else policy.compute
    D = pairwise_dist(coords, qc, compute_dtype=compute)  # one stacked matmul
    if inv is not None:
        D = D[:, inv]                                    # re-expand dedup
    D = annotate.emd_stacked_dist(D.reshape(v, nq, h))
    D = jnp.where(Q_w[None] > 0.0, D, pad_dist_for(policy.storage))
    return D.astype(policy.storage)


@scopes.scoped(scopes.PHASE1)
def phase1_batched(coords: Array, Q_ids: Array, Q_w: Array, k: int,
                   precision: str = "f32"):
    """Batched Phase 1: stacked distance tensor + single-pass top-k.

    The per-query top-k runs on the (v, nq, h) view of the one stacked
    matmul. Returns the query-major handoff ladders Z, W of shape
    (nq, v, k), pinned to their Phase-2 layout (queries on their DP
    shards, ladders replicated — the all-gather over "model").

    Selection (and its winner-masking sentinel writes) runs in the
    policy's float32 accumulator dtype — the bf16 -> f32 upcast is exact,
    so the selected (value, index) registers are identical to selecting
    on the storage values — and the handoff ladders are downcast to the
    storage dtype only after it.
    """
    policy = resolve_precision(precision)
    D = phase1_stacked_dist(coords, Q_ids, Q_w, precision=precision)
    Z, S = streaming_smallest_k(_accum(D), k)            # (v, nq, k)
    Zq = annotate.emd_ladder(
        jnp.moveaxis(Z, 1, 0).astype(policy.storage))    # (nq, v, k)
    Sq = jnp.moveaxis(S, 1, 0)
    W = annotate.emd_ladder(
        take_bins(Q_w, Sq).astype(policy.storage))
    return Zq, W


@scopes.scoped(scopes.PHASE1)
def _min_handoff(D: Array) -> Array:
    """(nq, v) masked-min handoff from the stacked (v, nq, h) Phase-1
    tensor, on the Phase-2 layout (single derivation point, shared by the
    directional and symmetric engines so the annotation cannot diverge)."""
    return annotate.emd_ladder(jnp.min(D, axis=-1).T)


@scopes.scoped(scopes.PHASE1)
def _rev_handoff(D: Array) -> Array:
    """(nq, v, h) query-major reverse-direction handoff from the stacked
    (v, nq, h) Phase-1 tensor, on the Phase-2 layout (single derivation
    point — see :func:`_min_handoff`)."""
    return annotate.emd_ladder(jnp.moveaxis(D, 1, 0))


@scopes.scoped(scopes.PHASE1)
def phase1_min_batched(coords: Array, Q_ids: Array, Q_w: Array,
                       precision: str = "f32") -> Array:
    """Masked-min Phase-1 fast path (LC-RWMD / zero Phase-2 rounds): only
    the nearest distance is ever read, so ranked (value, index) registers
    and the W capacities are skipped entirely — one stacked matmul, one
    row-min. Returns the (nq, v) handoff on the Phase-2 layout (in the
    policy's storage dtype — a min selects an existing value, so it is
    safe directly on the reduced-precision tensor)."""
    return _min_handoff(phase1_stacked_dist(coords, Q_ids, Q_w,
                                            precision=precision))


def pour(x: Array, Zg: Array, Wg: Array, iters: int) -> Array:
    """Phases 2+3 as a single fused pour over padded entries.

    x:  (..., hmax) residual database weights.
    Zg: (..., hmax, iters+1) ascending per-entry transport costs.
    Wg: (..., hmax, iters)   per-entry capacities (query weights).
    Returns (...,) transport-cost lower bounds.

    The per-entry greedy pour is the same exclusive-prefix-sum trick as
    ``relaxations._greedy_pour_rows`` — mathematically identical to the
    paper's k-1 sequential min/subtract rounds, but reads x once.
    """
    if iters == 0:
        return jnp.sum(x * Zg[..., 0], axis=-1)
    prefix = jnp.cumsum(Wg, axis=-1) - Wg                # exclusive prefix
    r = jnp.clip(x[..., None] - prefix, 0.0, Wg)         # (..., hmax, iters)
    poured = jnp.sum(r * Zg[..., :iters], axis=(-1, -2))
    remainder = jnp.maximum(x - jnp.sum(r, axis=-1), 0.0)
    return poured + jnp.sum(remainder * Zg[..., iters], axis=-1)


@functools.partial(jax.jit, static_argnames=("iters", "use_kernels",
                                             "block_v", "block_h", "block_n"))
def lc_act_scores(corpus: Corpus, q_ids: Array, q_w: Array, iters: int = 1,
                  *, use_kernels: bool = False, block_v: int = 256,
                  block_h: int = 256, block_n: int = 256) -> Array:
    """LC-ACT: lower bounds on EMD(x_u, q) — cost of moving each database
    histogram INTO the query — for all n database rows. O(vhm + nhk).

    ``use_kernels`` routes both phases through the fused Pallas kernels
    (``kernels/dist_topk``, ``kernels/act_phase2``) with the given block
    sizes; otherwise the pjit-able jnp reference path runs.
    """
    k = iters + 1
    if use_kernels:
        from repro.kernels import ops as kops
        with jax.named_scope(scopes.PHASE1):
            Z, S = kops.dist_topk(corpus.coords, corpus.coords[q_ids], k,
                                  qmask=(q_w > 0.0), block_v=block_v,
                                  block_h=block_h)
            W = take_bins(q_w, S)
    else:
        Z, W = phase1(corpus.coords, q_ids, q_w, k)
    with jax.named_scope(scopes.PHASE2):
        if iters >= 1 and use_kernels:
            # rung-major gathers: (k, n, hmax) / (iters, n, hmax)
            with jax.named_scope(scopes.LADDER_GATHER):
                Zg, Wg = Z.T[:, corpus.ids], W.T[:iters][:, corpus.ids]
            return kops.act_phase2(corpus.w, Zg, Wg, block_n=block_n,
                                   block_h=block_h)
        with jax.named_scope(scopes.LADDER_GATHER):
            Zg = Z[corpus.ids]                           # (n, hmax, k)
        if iters == 0:
            return jnp.sum(corpus.w * Zg[..., 0], axis=-1)
        with jax.named_scope(scopes.LADDER_GATHER):
            Wg = W[corpus.ids][..., :iters]              # (n, hmax, iters)
        return pour(corpus.w, Zg, Wg, iters)


@functools.partial(jax.jit, static_argnames=("use_kernels", "block_v",
                                             "block_h"))
def lc_rwmd_scores(corpus: Corpus, q_ids: Array, q_w: Array, *,
                   use_kernels: bool = False, block_v: int = 256,
                   block_h: int = 256) -> Array:
    """LC-RWMD direction db -> query (== LC-ACT with zero Phase-2 rounds)."""
    return lc_act_scores(corpus, q_ids, q_w, iters=0, use_kernels=use_kernels,
                         block_v=block_v, block_h=block_h)


@functools.partial(jax.jit, static_argnames=("block",))
def lc_rwmd_scores_rev(corpus: Corpus, q_ids: Array, q_w: Array,
                       block: int = 256) -> Array:
    """LC-RWMD direction query -> db: each query bin ships to the nearest
    coordinate PRESENT in each database histogram.

    This is the 2017 paper's masked (min,+) sparse-dense product, expressed
    on the padded layout: for db row u and query bin j,
        c[u, j] = min over valid slots s of D[ids[u, s], j].
    Work is O(n * hmax * h) element-wise minima — the quadratic-in-h term
    LC-RWMD tolerates because it is pure VPU streaming (no matmul, no sort).
    Processed in row blocks to bound memory.
    """
    qc = corpus.coords[q_ids]                            # (h, m)
    D = pairwise_dist(corpus.coords, qc)                 # (v, h)
    valid = corpus.w > 0.0                               # (n, hmax)
    # The finite sentinel, not inf, matching the batched rev engines: an
    # all-padding db row then scores huge-but-finite instead of NaN
    # (inf * a weight-0 query bin), so the scan oracle agrees with them
    # on padded corpora.
    big = _pad_const(D.dtype)

    def one_block(ids_blk, valid_blk):
        Dg = D[ids_blk]                                  # (b, hmax, h)
        Dg = jnp.where(valid_blk[..., None], Dg, big)
        cmin = jnp.min(Dg, axis=1)                       # (b, h)
        return jnp.matmul(cmin, q_w,
                          precision=matmul_precision(cmin.dtype))  # (b,)

    n = corpus.n
    pad = (-n) % block
    ids_p = jnp.pad(corpus.ids, ((0, pad), (0, 0)))
    valid_p = jnp.pad(valid, ((0, pad), (0, 0)), constant_values=True)
    out = jax.lax.map(
        lambda args: one_block(*args),
        (ids_p.reshape(-1, block, corpus.hmax), valid_p.reshape(-1, block, corpus.hmax)),
    )
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("use_kernels", "block_v",
                                             "block_h"))
def lc_omr_scores(corpus: Corpus, q_ids: Array, q_w: Array, *,
                  use_kernels: bool = False, block_v: int = 256,
                  block_h: int = 256) -> Array:
    """LC-OMR: Algorithm 1 batched over the corpus (top-2 per vocab row)."""
    if use_kernels:
        from repro.kernels import ops as kops
        with jax.named_scope(scopes.PHASE1):
            Z, S = kops.dist_topk(corpus.coords, corpus.coords[q_ids], 2,
                                  qmask=(q_w > 0.0), block_v=block_v,
                                  block_h=block_h)
            W = take_bins(q_w, S)
    else:
        Z, W = phase1(corpus.coords, q_ids, q_w, 2)
    with jax.named_scope(scopes.PHASE2):
        with jax.named_scope(scopes.LADDER_GATHER):
            Zg = Z[corpus.ids]                           # (n, hmax, 2)
            W0g = W[corpus.ids][..., 0]                  # one gather each
        x = corpus.w
        overlap = Zg[..., 0] == 0.0
        rest = x - jnp.minimum(x, W0g)
        per_entry = jnp.where(overlap, rest * Zg[..., 1], x * Zg[..., 0])
        return jnp.sum(per_entry, axis=-1)


# --------------------------------------------------------------------------
# Batched multi-query pipeline: the query batch is a first-class axis.
#
# The pipeline is three composable stages with EXPLICIT handoff arrays, so
# the single-host engines below and the distributed step in
# ``launch/search.py`` run the SAME code (the stages carry their own
# ``sharding.annotate`` constraints, which no-op outside a mesh):
#
#   stage 1  phase1_stacked_dist / phase1_batched / phase1_min_batched
#            -> handoff: (v, nq, h) D, (nq, v, k) Z/W, or (nq, v) Z0
#   stage 2  pour_blocked / pour_min_blocked / omr_reduce_blocked /
#            rev_min_blocked — query-blocked Phase 2/3 consumers of the
#            handoff; the (nq, n, hmax, k) gather tensor never
#            materializes.
#   stage 3  (callers) ranking / symmetrization on the (nq, n) scores.
# --------------------------------------------------------------------------


def _map_query_blocks(fn, arrays, nq: int, block_q: int):
    """``lax.map`` ``fn`` over blocks of ``block_q`` queries.

    Each array has leading query axis ``nq``; the axis is zero-padded to a
    block multiple (padding scores are dropped) and ``fn`` receives one
    ``(block_q, ...)`` slice per array. Output re-flattened to (nq, ...).
    A batch that fits one block runs ``fn`` directly, fully vectorized.
    """
    if nq <= block_q:
        return fn(*arrays)
    pad = (-nq) % block_q
    padded = tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                   for a in arrays)
    blocked = tuple(a.reshape((-1, block_q) + a.shape[1:]) for a in padded)
    # Reduced-precision handoffs (a policy's bf16 storage) enter the
    # scan BITCAST to a same-width unsigned integer and come back to
    # their float dtype inside the body: the consumers upcast to their
    # f32 accumulator first thing, and XLA otherwise hoists that convert
    # out of the loop — ahead of the scan-axis resharding — so the mesh
    # gathers full-width f32 again. A float convert cannot commute
    # across the bitcast. Float32 inputs take the original body
    # (bitwise-identical graphs).
    def _fence(a):
        if jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != jnp.float32:
            return jax.lax.bitcast_convert_type(
                a, jnp.dtype(f"uint{a.dtype.itemsize * 8}"))
        return a

    dtypes = tuple(a.dtype for a in blocked)
    fenced = tuple(_fence(a) for a in blocked)

    def body(args):
        return fn(*(jax.lax.bitcast_convert_type(a, dt)
                    if a.dtype != dt else a
                    for a, dt in zip(args, dtypes)))

    out = jax.lax.map(body, fenced)
    return out.reshape((-1,) + out.shape[2:])[:nq]


def _phase1_batched_dispatch(corpus: Corpus, Q_ids: Array, Q_w: Array,
                             k: int, use_kernels: bool, block_v: int,
                             block_h: int, mesh=None,
                             precision: str = "f32"):
    """Batched Phase 1 via the fused Pallas kernel or the jnp reference.
    Returns query-major Z (nq, v, k) and W on the handoff layout; the
    kernel paths keep only the k-1 capacity columns the reductions read
    (the last rung takes the Phase-3 dump), so no unread column crosses
    the mesh.
    On a ``mesh`` whose DP axes divide the queries the kernel runs inside
    a ``shard_map`` partitioning shim (vocabulary over "model").
    ``precision`` threads the policy's compute dtype into the kernel's
    matmul operands and its storage dtype into the handoff ladders
    (``out_dtype`` — the kernel's Z block buffers shrink with it)."""
    if not use_kernels:
        return phase1_batched(corpus.coords, Q_ids, Q_w, k,
                              precision=precision)
    from repro.kernels import ops as kops
    with jax.named_scope(scopes.PHASE1):
        policy = resolve_precision(precision)
        coords, qcs = corpus.coords, corpus.coords[Q_ids]
        if policy.compute != "float32":
            coords = coords.astype(policy.compute)
            qcs = qcs.astype(policy.compute)
        if mesh is not None:
            from repro.kernels import partition
            if partition.queries_shardable(mesh, Q_ids.shape[0]):
                Z, W = partition.dist_topk_sharded(
                    mesh, coords, qcs, Q_w, k,
                    block_v=block_v, block_h=block_h,
                    out_dtype=policy.storage)
                return annotate.emd_ladder(Z), annotate.emd_ladder(W)
        Z, S = kops.dist_topk_batched(coords, qcs, k,
                                      qmask=(Q_w > 0.0), block_v=block_v,
                                      block_h=block_h,
                                      out_dtype=policy.storage)
        W = take_bins(Q_w, S[..., :k - 1]).astype(policy.storage)
        return annotate.emd_ladder(Z), annotate.emd_ladder(W)


@scopes.scoped(scopes.PHASE2)
def pour_min_blocked(corpus: Corpus, Z0: Array, block_q: int) -> Array:
    """Zero-round Phase 2 on the masked-min handoff: each block of
    ``block_q`` queries gathers its (bq, n, hmax) nearest-distance slice
    once and reduces. Z0: (nq, v) -> (nq, n) scores."""
    def blk(Zb):                                         # (bq, v)
        with jax.named_scope(scopes.LADDER_GATHER):
            Zg = Zb[:, corpus.ids]
        return jnp.sum(corpus.w * Zg, axis=-1)
    return _map_query_blocks(blk, (Z0,), Z0.shape[0], block_q)


@scopes.scoped(scopes.PHASE2)
def pour_blocked(corpus: Corpus, Z: Array, W: Array, iters: int,
                 block_q: int, *, use_kernels: bool = False,
                 block_n: int = 256, block_h: int = 256, mesh=None,
                 segments: Segments | None = None) -> Array:
    """Query-blocked Phase 2/3 pour: (nq, v, k) handoff ladders ->
    (nq, n) lower bounds. Each block of ``block_q`` queries gathers its
    (bq, n, hmax, k) cost/capacity ladders once and pours (fused Pallas
    kernel when ``use_kernels``); ``iters=0`` degenerates to the
    nearest-cost dump of Phase 3. On a ``mesh`` whose axes divide, the
    kernel path runs inside a ``shard_map`` shim with the query blocking
    per shard (queries over DP, database rows over "model").

    Given ``segments`` (the corpus's :class:`Segments`), the single-device
    kernel path gathers from that layout instead: (bq, k, S, width)
    ladders for the real bins' segments only, poured at ``block_h`` =
    the segment width into (bq, S) segment costs, which are summed into
    rows. The reference branch and ``iters=0`` always read the padded
    (n, hmax) rows."""
    nq = Z.shape[0]
    x = corpus.w
    if iters == 0:
        def blk0(Zb):                                    # (bq, v, k)
            with jax.named_scope(scopes.LADDER_GATHER):
                Zg = Zb[..., 0][:, corpus.ids]
            return jnp.sum(x * Zg, axis=-1)
        return _map_query_blocks(blk0, (Z,), nq, block_q)
    W = W[..., :iters]
    if use_kernels:
        from repro.kernels import ops as kops
        if mesh is not None:
            from repro.kernels import partition
            if partition.rows_shardable(mesh, nq, corpus.n):
                return partition.act_pour_sharded(
                    mesh, corpus.ids, corpus.w, Z, W, iters,
                    block_q=block_q, block_n=block_n, block_h=block_h)

        if segments is not None:
            def blk_s(Zb, Wb):
                # rung-major gathers over segments: (bq, k, S, width)
                with jax.named_scope(scopes.LADDER_GATHER):
                    Zg = jnp.swapaxes(Zb, 1, 2)[:, :, segments.ids]
                    Wg = jnp.swapaxes(Wb, 1, 2)[:, :, segments.ids]
                return kops.act_phase2_batched(segments.w, Zg, Wg,
                                               block_n=block_n,
                                               block_h=segments.width)
            t = _map_query_blocks(blk_s, (Z, W), nq, block_q)   # (nq, S)
            # (nq, n, c) segment costs of each row, once for the whole
            # batch; no segment reads 0
            return jnp.take(t, segments.rows, axis=1, mode="fill",
                            fill_value=0.0).sum(axis=-1)

        def blk_k(Zb, Wb):
            # rung-major gathers for the kernel: (bq, k, n, hmax)
            with jax.named_scope(scopes.LADDER_GATHER):
                Zg = jnp.swapaxes(Zb, 1, 2)[:, :, corpus.ids]
                Wg = jnp.swapaxes(Wb, 1, 2)[:, :, corpus.ids]
            return kops.act_phase2_batched(x, Zg, Wg, block_n=block_n,
                                           block_h=block_h)
        return _map_query_blocks(blk_k, (Z, W), nq, block_q)

    def blk(Zb, Wb):
        # Gather in storage dtype (half the HBM traffic under bf16),
        # pour in the f32 accumulator dtype (cumsum/clip never run on
        # bf16). Both upcasts are no-ops for the default f32 policy.
        with jax.named_scope(scopes.LADDER_GATHER):
            Zg = _accum(Zb[:, corpus.ids])               # (bq, n, hmax, k)
            Wg = _accum(Wb[:, corpus.ids])               # (bq, n, hmax, iters)
        return pour(x, Zg, Wg, iters)                    # (bq, n)
    return _map_query_blocks(blk, (Z, W), nq, block_q)


@scopes.scoped(scopes.PHASE2)
def omr_reduce_blocked(corpus: Corpus, Z: Array, W0: Array,
                       block_q: int) -> Array:
    """Query-blocked Algorithm-1 reduction on the top-2 handoff:
    Z (nq, v, 2), W0 (nq, v) -> (nq, n) LC-OMR bounds."""
    x = corpus.w

    def blk(Zb, W0b):                                    # (bq, v, 2), (bq, v)
        with jax.named_scope(scopes.LADDER_GATHER):
            Zg = Zb[:, corpus.ids]                       # (bq, n, hmax, 2)
            W0g = W0b[:, corpus.ids]                     # (bq, n, hmax)
        overlap = Zg[..., 0] == 0.0
        rest = x - jnp.minimum(x, W0g)
        per_entry = jnp.where(overlap, rest * Zg[..., 1], x * Zg[..., 0])
        return jnp.sum(per_entry, axis=-1)
    return _map_query_blocks(blk, (Z, W0), Z.shape[0], block_q)


@scopes.scoped(scopes.PHASE2)
def rev_min_blocked(corpus: Corpus, Dq: Array, Q_w: Array, block: int,
                    block_q: int) -> Array:
    """Reverse-direction masked (min,+) reduction on the query-major
    distance handoff Dq (nq, v, h): for db row u and query bin j,
    c[u, j] = min over valid slots s of Dq[:, ids[u, s], j], streamed in
    (row-block, query-block) tiles so the (nq, n, hmax, h) gather never
    materializes. Invalid slots mask to PAD_DIST (finite — all-padding
    rows score huge instead of NaN when a padded query bin's weight-0
    product would otherwise hit inf * 0). Sentinel masking and the
    (min,+) contraction run in the f32 accumulator dtype (the gather
    itself stays in the handoff's storage dtype)."""
    valid = corpus.w > 0.0                               # (n, hmax)
    acc = jnp.promote_types(Dq.dtype, jnp.float32)
    big = _pad_const(acc)
    n = corpus.n
    pad = (-n) % block
    ids_b = jnp.pad(corpus.ids, ((0, pad), (0, 0))).reshape(-1, block,
                                                            corpus.hmax)
    valid_b = jnp.pad(valid, ((0, pad), (0, 0)),
                      constant_values=True).reshape(-1, block, corpus.hmax)

    def qblock(Db, Wb):                                  # (bq, v, h), (bq, h)
        def rblock(args):
            ids_blk, valid_blk = args
            with jax.named_scope(scopes.LADDER_GATHER):
                Dg = _accum(Db[:, ids_blk])              # (bq, b, hmax, h)
            Dg = jnp.where(valid_blk[None, ..., None], Dg, big)
            cmin = jnp.min(Dg, axis=2)                   # (bq, b, h)
            return jnp.einsum("qbh,qh->qb", cmin, Wb,
                              precision=matmul_precision(cmin.dtype))
        out = jax.lax.map(rblock, (ids_b, valid_b))      # (nrb, bq, b)
        return jnp.moveaxis(out, 1, 0).reshape(Db.shape[0], -1)[:, :n]
    return _map_query_blocks(qblock, (Dq, Q_w), Dq.shape[0], block_q)


@scopes.scoped(scopes.PHASE2)
def rev_min_full(corpus: Corpus, Dq: Array, Q_w: Array,
                 block_q: int) -> Array:
    """Mesh variant of :func:`rev_min_blocked`: no row-blocking ``lax.map``
    (XLA SPMD cannot iterate a scan over the "model"-sharded row axis
    without gathering it), so the (bq, n, hmax, h) gather stays on the
    model shards and memory is bounded by the query blocks alone."""
    valid = corpus.w > 0.0
    acc = jnp.promote_types(Dq.dtype, jnp.float32)
    big = _pad_const(acc)

    def qblock(Db, Wb):                                  # (bq, v, h), (bq, h)
        with jax.named_scope(scopes.LADDER_GATHER):
            Dg = _accum(Db[:, corpus.ids])
        Dg = jnp.where(valid[None, ..., None], Dg, big)
        cmin = jnp.min(Dg, axis=2)                       # (bq, n, h)
        return jnp.einsum("qnh,qh->qn", cmin, Wb,
                          precision=matmul_precision(cmin.dtype))
    return _map_query_blocks(qblock, (Dq, Q_w), Dq.shape[0], block_q)


# ------------------------------------------------------- batched engines


@functools.partial(jax.jit, static_argnames=("iters", "use_kernels",
                                             "block_q", "block_v", "block_h",
                                             "block_n", "mesh", "precision"))
def lc_act_scores_batched(corpus: Corpus, Q_ids: Array, Q_w: Array,
                          iters: int = 1, *, use_kernels: bool = False,
                          block_q: int = 8, block_v: int = 256,
                          block_h: int = 256, block_n: int = 256,
                          mesh=None, precision: str = "f32",
                          segments: Segments | None = None) -> Array:
    """Batched LC-ACT: (nq, h) query batch -> (nq, n) lower bounds
    (stage-1 ranked Phase 1 composed with the query-blocked pour).
    ``mesh`` (static, hashable) routes the kernel path through the
    ``kernels/partition`` shard_map shims when its axes divide;
    ``precision`` (static policy name) sets the handoff storage / matmul
    compute dtypes — reductions always accumulate in float32.
    ``segments``: the corpus's segmented layout, which the single-device
    kernel pour gathers from (:func:`pour_blocked`)."""
    if iters == 0 and not use_kernels:
        Z0 = phase1_min_batched(corpus.coords, Q_ids, Q_w,
                                precision=precision)
        return pour_min_blocked(corpus, Z0, block_q)
    Z, W = _phase1_batched_dispatch(corpus, Q_ids, Q_w, iters + 1,
                                    use_kernels, block_v, block_h, mesh,
                                    precision=precision)
    return pour_blocked(corpus, Z, W, iters, block_q,
                        use_kernels=use_kernels, block_n=block_n,
                        block_h=block_h, mesh=mesh, segments=segments)


@functools.partial(jax.jit, static_argnames=("use_kernels", "block_q",
                                             "block_v", "block_h", "mesh",
                                             "precision"))
def lc_rwmd_scores_batched(corpus: Corpus, Q_ids: Array, Q_w: Array, *,
                           use_kernels: bool = False, block_q: int = 8,
                           block_v: int = 256, block_h: int = 256,
                           mesh=None, precision: str = "f32") -> Array:
    """Batched LC-RWMD db -> query (== batched LC-ACT with zero rounds)."""
    return lc_act_scores_batched(corpus, Q_ids, Q_w, iters=0,
                                 use_kernels=use_kernels, block_q=block_q,
                                 block_v=block_v, block_h=block_h, mesh=mesh,
                                 precision=precision)


def _rows_model_sharded() -> bool:
    """True when the ambient mesh actually splits database rows over
    "model" — the precondition for :func:`rev_min_full`'s memory bound.
    On a model-size-1 mesh (or outside any mesh / on jax without an
    ambient-mesh API) the full-row gather would sit on ONE device, so
    callers must keep the row-blocked schedule instead."""
    mesh = annotate.current_mesh()
    return mesh is not None and mesh.shape.get("model", 1) > 1


@functools.partial(jax.jit, static_argnames=("block", "block_q",
                                             "precision"))
def lc_rwmd_scores_rev_batched(corpus: Corpus, Q_ids: Array, Q_w: Array,
                               block: int = 256, block_q: int = 8,
                               precision: str = "f32") -> Array:
    """Batched LC-RWMD query -> db: one stacked distance tensor for the
    WHOLE batch, streamed through the (row-block, query-block) masked
    (min,+) reduction."""
    Dq = _rev_handoff(phase1_stacked_dist(corpus.coords, Q_ids, Q_w,
                                          precision=precision))
    return rev_min_blocked(corpus, Dq, Q_w, block, block_q)


@functools.partial(jax.jit, static_argnames=("block", "block_q",
                                             "precision"))
def lc_rwmd_scores_rev_dist(corpus: Corpus, Q_ids: Array, Q_w: Array, *,
                            block: int = 256, block_q: int = 8,
                            precision: str = "f32") -> Array:
    """Mesh-sharded batched LC-RWMD query -> db: same stacked Phase 1, but
    when database rows are genuinely split over "model" the reduction
    keeps them on their shards (:func:`rev_min_full`) instead of scanning
    row blocks — the row scan would force XLA to gather the sharded rows
    onto every device. Without real model sharding (single-device default
    mesh) the full-row gather has nothing bounding it, so the row-blocked
    schedule is kept."""
    Dq = _rev_handoff(phase1_stacked_dist(corpus.coords, Q_ids, Q_w,
                                          precision=precision))
    if _rows_model_sharded():
        return rev_min_full(corpus, Dq, Q_w, block_q)
    return rev_min_blocked(corpus, Dq, Q_w, block, block_q)


@functools.partial(jax.jit, static_argnames=("use_kernels", "block_q",
                                             "block_v", "block_h", "mesh",
                                             "precision"))
def lc_omr_scores_batched(corpus: Corpus, Q_ids: Array, Q_w: Array, *,
                          use_kernels: bool = False, block_q: int = 8,
                          block_v: int = 256, block_h: int = 256,
                          mesh=None, precision: str = "f32") -> Array:
    """Batched LC-OMR: shared batched Phase 1 (top-2 per vocabulary row),
    query-blocked Algorithm-1 reduction."""
    Z, W = _phase1_batched_dispatch(corpus, Q_ids, Q_w, 2, use_kernels,
                                    block_v, block_h, mesh,
                                    precision=precision)
    return omr_reduce_blocked(corpus, Z, W[..., 0], block_q)


@functools.partial(jax.jit, static_argnames=("block", "block_q",
                                             "full_rows", "precision"))
def lc_rwmd_symmetric_scores_batched(corpus: Corpus, Q_ids: Array,
                                     Q_w: Array, *, block: int = 256,
                                     block_q: int = 8,
                                     full_rows: bool = False,
                                     precision: str = "f32") -> Array:
    """Symmetric batched LC-RWMD: max of the two directional bounds
    sharing ONE stacked Phase-1 distance tensor — the forward masked-min
    row and the reverse (min,+) reduction both read the same (v, nq, h) D
    (previously each direction recomputed the (v, nq*h) matmul).
    ``full_rows`` requests the mesh-friendly reverse reduction (honored
    only when rows are really model-sharded; see
    :func:`_rows_model_sharded`)."""
    D = phase1_stacked_dist(corpus.coords, Q_ids, Q_w, precision=precision)
    fwd = pour_min_blocked(corpus, _min_handoff(D), block_q)
    Dq = _rev_handoff(D)                                 # (nq, v, h)
    rev = (rev_min_full(corpus, Dq, Q_w, block_q)
           if full_rows and _rows_model_sharded()
           else rev_min_blocked(corpus, Dq, Q_w, block, block_q))
    return jnp.maximum(fwd, rev)


def symmetric_scores(asym: Array) -> Array:
    """Corpus-vs-corpus symmetrization: asym[a, b] = cost(move b into a);
    the paper's symmetric measure is max(asym, asym.T)."""
    return jnp.maximum(asym, asym.T)


# --------------------------------------------------------------------------
# LC-ICT: the paper's tightest linear-complexity bound (Algorithm 2), as a
# batch engine. ICT pours each database entry's mass through the FULL
# cost-sorted ladder of query bins (not a truncated top-k), so Phase 2 is a
# per-entry sort over h instead of the k-register selection — O(n h log h)
# on top of the shared Phase-1 distance work. It exists here primarily as a
# cascade rescorer: too expensive for full-corpus serving, ideal on a
# pruned candidate set.
# --------------------------------------------------------------------------


def ict_pour(x: Array, cap: Array, C: Array) -> Array:
    """Full-ladder greedy pour (Algorithm 2) over padded entries.

    x:   (..., hmax) residual database weights.
    cap: (..., hmax, h) per-edge capacities (query weights; 0 at padded
         query bins).
    C:   (..., hmax, h) transport costs (PAD_DIST at padded query bins, so
         they sort last and their zero capacity absorbs nothing).
    Returns (...,) transport-cost bounds.

    L1-normalized histograms leave no remainder; any float residue is
    dumped at the max FINITE cost — never at PAD_DIST, where a ~1e-7
    cumsum residue would explode to ~1e23 (the reason this does not reuse
    ``relaxations.ict_dir``'s last-slot dump on padded layouts).
    """
    order = jnp.argsort(C, axis=-1)
    cost_sorted = jnp.take_along_axis(C, order, axis=-1)
    cap_sorted = jnp.take_along_axis(cap, order, axis=-1)
    prefix = jnp.cumsum(cap_sorted, axis=-1) - cap_sorted  # exclusive prefix
    r = jnp.clip(x[..., None] - prefix, 0.0, cap_sorted)
    poured = jnp.sum(r * cost_sorted, axis=-1)
    remainder = jnp.maximum(x - jnp.sum(r, axis=-1), 0.0)
    # Strict < : sentinel entries (written in any storage dtype, upcast
    # or not) compare >= their dtype's pad value and are excluded.
    dump = jnp.max(jnp.where(C < _pad_const(C.dtype), C, 0.0), axis=-1)
    return jnp.sum(poured + remainder * dump, axis=-1)


def _ict_caps(Q_w: Array, shape) -> Array:
    """Broadcast (…, h) query weights to the (…, hmax, h) per-edge
    capacity tensor of :func:`ict_pour`."""
    return jnp.broadcast_to(Q_w[..., None, :], shape)


@jax.jit
def lc_ict_scores(corpus: Corpus, q_ids: Array, q_w: Array) -> Array:
    """LC-ICT: Algorithm 2 batched over the corpus — lower bounds on
    EMD(x_u, q) for all n database rows, O(vhm + n hmax h log h)."""
    qc = corpus.coords[q_ids]                            # (h, m)
    D = pairwise_dist(corpus.coords, qc)                 # (v, h)
    D = jnp.where(q_w[None, :] > 0.0, D, pad_dist_for(D.dtype))
    C = D[corpus.ids]                                    # (n, hmax, h)
    return ict_pour(corpus.w, _ict_caps(q_w, C.shape), C)


@scopes.scoped(scopes.PHASE2)
def ict_reduce_blocked(corpus: Corpus, Dq: Array, Q_w: Array,
                       block_q: int) -> Array:
    """Query-blocked Algorithm-2 reduction on the query-major distance
    handoff Dq (nq, v, h) -> (nq, n) LC-ICT bounds. Each block of
    ``block_q`` queries gathers its (bq, n, hmax, h) cost tensor once and
    pours through the full sorted ladder."""
    def blk(Db, Wb):                                     # (bq, v, h), (bq, h)
        # Gather in storage dtype, sort + pour the ladder in the f32
        # accumulator (the sort itself is exact in any dtype, but the
        # pour's cumulative caps are not).
        with jax.named_scope(scopes.LADDER_GATHER):
            C = _accum(Db[:, corpus.ids])                # (bq, n, hmax, h)
        cap = _ict_caps(Wb[:, None, :], C.shape)
        return ict_pour(corpus.w, cap, C)
    return _map_query_blocks(blk, (Dq, Q_w), Dq.shape[0], block_q)


@functools.partial(jax.jit, static_argnames=("block_q", "precision"))
def lc_ict_scores_batched(corpus: Corpus, Q_ids: Array, Q_w: Array, *,
                          block_q: int = 8, precision="f32") -> Array:
    """Batched LC-ICT: one stacked Phase-1 distance tensor for the whole
    query batch, query-blocked full-ladder pour."""
    Dq = _rev_handoff(phase1_stacked_dist(corpus.coords, Q_ids, Q_w,
                                          precision=precision))
    return ict_reduce_blocked(corpus, Dq, Q_w, block_q)


# --------------------------------------------------------------------------
# Candidate-compacted Phase 2/3: the cascade's gather-compaction layer.
#
# A prune-and-rescore cascade (``repro.cascade``) scores stage s+1 only on
# the (nq, b) candidate rows that survived stage s. Phase 1 is UNCHANGED —
# the vocabulary-vs-query work never depends on which database rows are
# scored — so candidate compaction is purely a Phase-2/3 concern: the same
# blocked consumers as above, but gathering each query's own (b, hmax)
# sub-corpus (``corpus.ids[cand[u]]`` — Corpus row-slicing with the padded
# layout preserved, no re-bucketing needed) instead of all n rows. Per
# (query, row) the reduction order matches the full-corpus consumers, so
# scores agree with the full engines at the candidate rows — bitwise for
# the ladder consumers; ``rev_min_cand_blocked`` is within an ulp of
# ``rev_min_blocked`` (its reduction is mul+sum where the full engine
# contracts with einsum — see the comment there for why).
#
# ``use_kernels`` routes each consumer through the candidate Pallas
# kernels (``kernels/cand_pour``), which take one of two gathers by the
# table's shape. The ladder consumers (pour, zero-round pour, OMR) have
# one table row per rung, so ``kernels/ops`` gathers each slot's rungs by
# id with XLA, rung-major (bq, rows, b, hp), under ``emd.ladder_gather``
# as the batch path does, and the kernel only reduces: the cost follows
# the candidates' slots, not the vocabulary. The distance consumers
# (reverse RWMD, ICT) have one row per query bin, and a gathered copy
# would be (bq, b, hmax, h), gigabytes at text widths; their kernel
# gathers in-kernel by one-hot products over vocabulary slabs, so that
# copy never exists. Phase 1 stays the shared jnp pipeline on BOTH paths
# and the kernels reuse the reference reductions (``pour``/``ict_pour``/
# the expressions below), so kernel and reference candidate scores agree
# to within a few ulps (both gathers are bitwise-exact) — the conformance
# contract ``tests/test_cand_kernels.py`` pins, with the residual ulp
# explained in ``kernels/cand_pour``'s module docstring.
# --------------------------------------------------------------------------


def gather_per_query(A: Array, idx: Array) -> Array:
    """Per-query gather: A (bq, v, ...) indexed on axis 1 by each query's
    own idx (bq, b, hmax) -> (bq, b, hmax, ...)."""
    return jax.vmap(lambda a, i: a[i])(A, idx)


@scopes.scoped(scopes.PHASE2)
def pour_min_cand_blocked(corpus: Corpus, Z0: Array, cand: Array,
                          block_q: int, *, use_kernels: bool = False,
                          block_n: int = 128, mesh=None) -> Array:
    """Candidate-compacted zero-round pour: Z0 (nq, v), cand (nq, b)
    -> (nq, b) scores at the candidate rows. ``use_kernels`` gathers the
    nearest costs by id and dumps them in a ``kernels/cand_pour`` launch
    (block_n candidate rows per tile); on a ``mesh`` whose DP axes divide
    the query batch, both run inside a ``shard_map`` shim with the
    sub-corpus gather kept outside."""
    if use_kernels:
        from repro.kernels import ops as kops
        if mesh is not None:
            from repro.kernels import partition
            if partition.queries_shardable(mesh, Z0.shape[0]):
                idsg, xg = corpus.ids[cand], corpus.w[cand]

                def sh_k(idsb, xb, Zb):
                    return kops.cand_pour(idsb, xb, Zb[..., None], None, 0,
                                          block_n=block_n)
                return partition.cand_sharded(mesh, sh_k, (idsg, xg, Z0),
                                              block_q)

        def blk_k(Zb, cb):                               # (bq, v), (bq, b)
            return kops.cand_pour(corpus.ids[cb], corpus.w[cb],
                                  Zb[..., None], None, 0, block_n=block_n)
        return _map_query_blocks(blk_k, (Z0, cand), Z0.shape[0], block_q)

    def blk(Zb, cb):                                     # (bq, v), (bq, b)
        with jax.named_scope(scopes.LADDER_GATHER):
            Zg = gather_per_query(Zb, corpus.ids[cb])   # (bq, b, hmax)
        return jnp.sum(corpus.w[cb] * Zg, axis=-1)
    return _map_query_blocks(blk, (Z0, cand), Z0.shape[0], block_q)


@scopes.scoped(scopes.PHASE2)
def pour_cand_blocked(corpus: Corpus, Z: Array, W: Array, cand: Array,
                      iters: int, block_q: int, *,
                      use_kernels: bool = False, block_n: int = 128,
                      mesh=None) -> Array:
    """Candidate-compacted Phase 2/3 pour: (nq, v, k) handoff ladders +
    (nq, b) candidate rows -> (nq, b) lower bounds. ``use_kernels``
    gathers the ladders by id and pours them in a ``kernels/cand_pour``
    launch (``shard_map`` shim on a dividing ``mesh``)."""
    nq = Z.shape[0]
    if iters == 0:
        return pour_min_cand_blocked(corpus, Z[..., 0], cand, block_q,
                                     use_kernels=use_kernels,
                                     block_n=block_n, mesh=mesh)
    W = W[..., :iters]
    if use_kernels:
        from repro.kernels import ops as kops
        if mesh is not None:
            from repro.kernels import partition
            if partition.queries_shardable(mesh, nq):
                idsg, xg = corpus.ids[cand], corpus.w[cand]

                def sh_k(idsb, xb, Zb, Wb):
                    return kops.cand_pour(idsb, xb, Zb, Wb, iters,
                                          block_n=block_n)
                return partition.cand_sharded(mesh, sh_k, (idsg, xg, Z, W),
                                              block_q)

        def blk_k(Zb, Wb, cb):
            return kops.cand_pour(corpus.ids[cb], corpus.w[cb], Zb, Wb,
                                  iters, block_n=block_n)
        return _map_query_blocks(blk_k, (Z, W, cand), nq, block_q)

    def blk(Zb, Wb, cb):
        ids_g = corpus.ids[cb]                           # (bq, b, hmax)
        # Gather in storage dtype; pour in the f32 accumulator (its
        # capacity cumsum must not round in bf16).
        with jax.named_scope(scopes.LADDER_GATHER):
            Zg = _accum(gather_per_query(Zb, ids_g))    # (bq, b, hmax, k)
            Wg = _accum(gather_per_query(Wb, ids_g))    # (bq, b, hmax, iters)
        return pour(corpus.w[cb], Zg, Wg, iters)         # (bq, b)
    return _map_query_blocks(blk, (Z, W, cand), nq, block_q)


@scopes.scoped(scopes.PHASE2)
def omr_reduce_cand_blocked(corpus: Corpus, Z: Array, W0: Array,
                            cand: Array, block_q: int, *,
                            use_kernels: bool = False, block_n: int = 128,
                            mesh=None) -> Array:
    """Candidate-compacted Algorithm-1 reduction: Z (nq, v, 2), W0 (nq, v),
    cand (nq, b) -> (nq, b) LC-OMR bounds. ``use_kernels`` gathers by id
    and reduces in a ``kernels/cand_pour`` launch (mode "omr";
    ``shard_map`` shim on a dividing ``mesh``)."""
    if use_kernels:
        from repro.kernels import ops as kops
        if mesh is not None:
            from repro.kernels import partition
            if partition.queries_shardable(mesh, Z.shape[0]):
                idsg, xg = corpus.ids[cand], corpus.w[cand]

                def sh_k(idsb, xb, Zb, W0b):
                    return kops.cand_omr(idsb, xb, Zb, W0b,
                                         block_n=block_n)
                return partition.cand_sharded(mesh, sh_k, (idsg, xg, Z, W0),
                                              block_q)

        def blk_k(Zb, W0b, cb):
            return kops.cand_omr(corpus.ids[cb], corpus.w[cb], Zb, W0b,
                                 block_n=block_n)
        return _map_query_blocks(blk_k, (Z, W0, cand), Z.shape[0], block_q)

    def blk(Zb, W0b, cb):
        ids_g = corpus.ids[cb]
        x = corpus.w[cb]                                 # (bq, b, hmax)
        with jax.named_scope(scopes.LADDER_GATHER):
            Zg = gather_per_query(Zb, ids_g)            # (bq, b, hmax, 2)
            W0g = gather_per_query(W0b, ids_g)          # (bq, b, hmax)
        overlap = Zg[..., 0] == 0.0
        rest = x - jnp.minimum(x, W0g)
        per_entry = jnp.where(overlap, rest * Zg[..., 1], x * Zg[..., 0])
        return jnp.sum(per_entry, axis=-1)
    return _map_query_blocks(blk, (Z, W0, cand), Z.shape[0], block_q)


@scopes.scoped(scopes.PHASE2)
def rev_min_cand_blocked(corpus: Corpus, Dq: Array, Q_w: Array,
                         cand: Array, block_q: int, *,
                         use_kernels: bool = False, block_n: int = 128,
                         block_v: int = 256, mesh=None) -> Array:
    """Candidate-compacted reverse masked (min,+) reduction: Dq (nq, v, h),
    cand (nq, b) -> (nq, b) reverse-RWMD bounds. ``use_kernels`` fuses
    gather + reduce into one ``kernels/cand_pour`` launch (``shard_map``
    shim on a dividing ``mesh``)."""
    if use_kernels:
        from repro.kernels import ops as kops
        if mesh is not None:
            from repro.kernels import partition
            if partition.queries_shardable(mesh, Dq.shape[0]):
                idsg, xg = corpus.ids[cand], corpus.w[cand]

                def sh_k(idsb, xb, Db, Wb):
                    return kops.cand_rev_min(idsb, xb, Db, Wb,
                                             block_n=block_n,
                                             block_v=block_v)
                return partition.cand_sharded(mesh, sh_k,
                                              (idsg, xg, Dq, Q_w), block_q)

        def blk_k(Db, Wb, cb):
            return kops.cand_rev_min(corpus.ids[cb], corpus.w[cb], Db, Wb,
                                     block_n=block_n, block_v=block_v)
        return _map_query_blocks(blk_k, (Dq, Q_w, cand), Dq.shape[0],
                                 block_q)
    # Mask + reduce in the accumulator dtype: the pad-row sentinel is
    # written in f32 (never a reduced storage dtype) so it cannot round
    # into the range of real costs.
    acc = jnp.promote_types(Dq.dtype, jnp.float32)
    big = _pad_const(acc)

    def blk(Db, Wb, cb):                                 # (bq, v, h), (bq, h)
        ids_g = corpus.ids[cb]                           # (bq, b, hmax)
        valid = corpus.w[cb] > 0.0
        with jax.named_scope(scopes.LADDER_GATHER):
            Dg = _accum(gather_per_query(Db, ids_g))    # (bq, b, hmax, h)
        Dg = jnp.where(valid[..., None], Dg, big)
        cmin = jnp.min(Dg, axis=2)                       # (bq, b, h)
        # multiply + last-axis reduce, NOT einsum: the dot op's
        # accumulation varies with the row count, so a candidate-blocked
        # kernel tile could never reproduce its bits — this form is
        # block-shape-stable (the kernel conformance contract).
        return jnp.sum(cmin * Wb[:, None, :], axis=-1)
    return _map_query_blocks(blk, (Dq, Q_w, cand), Dq.shape[0], block_q)


@scopes.scoped(scopes.PHASE2)
def ict_reduce_cand_blocked(corpus: Corpus, Dq: Array, Q_w: Array,
                            cand: Array, block_q: int, *,
                            use_kernels: bool = False, block_n: int = 128,
                            block_v: int = 256, mesh=None) -> Array:
    """Candidate-compacted Algorithm-2 reduction: Dq (nq, v, h),
    cand (nq, b) -> (nq, b) LC-ICT bounds. ``use_kernels`` fuses gather +
    full-ladder pour into one ``kernels/cand_pour`` launch (``shard_map``
    shim on a dividing ``mesh``); both paths run :func:`ict_pour`, so the
    remainder dump stays at the max FINITE cost (a PAD_DIST dump would
    explode float residue — see its doc)."""
    if use_kernels:
        from repro.kernels import ops as kops
        if mesh is not None:
            from repro.kernels import partition
            if partition.queries_shardable(mesh, Dq.shape[0]):
                idsg, xg = corpus.ids[cand], corpus.w[cand]

                def sh_k(idsb, xb, Db, Wb):
                    return kops.cand_ict(idsb, xb, Db, Wb, block_n=block_n,
                                         block_v=block_v)
                return partition.cand_sharded(mesh, sh_k,
                                              (idsg, xg, Dq, Q_w), block_q)

        def blk_k(Db, Wb, cb):
            return kops.cand_ict(corpus.ids[cb], corpus.w[cb], Db, Wb,
                                 block_n=block_n, block_v=block_v)
        return _map_query_blocks(blk_k, (Dq, Q_w, cand), Dq.shape[0],
                                 block_q)

    def blk(Db, Wb, cb):
        ids_g = corpus.ids[cb]
        # Gather in storage dtype; ladder pour in the f32 accumulator.
        with jax.named_scope(scopes.LADDER_GATHER):
            C = _accum(gather_per_query(Db, ids_g))     # (bq, b, hmax, h)
        cap = _ict_caps(Wb[:, None, :], C.shape)
        return ict_pour(corpus.w[cb], cap, C)
    return _map_query_blocks(blk, (Dq, Q_w, cand), Dq.shape[0], block_q)


# ------------------------------------------- candidate-compacted engines
#
# ``use_kernels`` on every engine routes Phase 2/3 through the fused
# candidate kernels; Phase 1 is the SAME shared jnp pipeline either way
# (the kernels fuse only the gather + reduction), so both paths score
# identically to within a few ulps at the candidate rows.


def _pin_handoff(*arrays):
    """Materialize the Phase-1 handoff behind an optimization barrier.

    The kernel and reference candidate paths are DIFFERENT XLA programs;
    without the barrier XLA fuses Phase 1 into whichever consumer follows
    (e.g. FMA-contracting the distance expansion), and the two programs
    would start from handoffs that already disagree by ulps. With it,
    Phase 1 compiles as the same standalone subgraph in both, so the
    handoff bits are identical and any residual divergence is confined
    to the reference reduction's own per-program fusion (a few ulps; see
    ``kernels/cand_pour``). Cost: the handoff materializes — it is the
    explicit stage boundary anyway (tiny next to Phase 2's reads).
    """
    out = jax.lax.optimization_barrier(arrays)
    return out[0] if len(arrays) == 1 else out


_CAND_STATIC = ("use_kernels", "block_q", "block_n", "mesh", "precision")
#: The one-hot candidate kernels (reverse RWMD, ICT) also tile the
#: vocabulary.
_CAND_DIST_STATIC = _CAND_STATIC + ("block_v",)


@functools.partial(jax.jit, static_argnames=("iters",) + _CAND_STATIC)
def lc_act_scores_cand(corpus: Corpus, Q_ids: Array, Q_w: Array,
                       cand: Array, iters: int = 1, *,
                       use_kernels: bool = False, block_q: int = 8,
                       block_n: int = 128, mesh=None,
                       precision="f32") -> Array:
    """Candidate-compacted batched LC-ACT: (nq, h) queries scored against
    each query's own (b,) candidate rows -> (nq, b)."""
    kw = dict(use_kernels=use_kernels, block_n=block_n, mesh=mesh)
    if iters == 0:
        Z0 = _pin_handoff(phase1_min_batched(corpus.coords, Q_ids, Q_w,
                                             precision=precision))
        return pour_min_cand_blocked(corpus, Z0, cand, block_q, **kw)
    Z, W = _pin_handoff(*phase1_batched(corpus.coords, Q_ids, Q_w,
                                        iters + 1, precision=precision))
    return pour_cand_blocked(corpus, Z, W, cand, iters, block_q, **kw)


@functools.partial(jax.jit, static_argnames=_CAND_STATIC)
def lc_rwmd_scores_cand(corpus: Corpus, Q_ids: Array, Q_w: Array,
                        cand: Array, *, use_kernels: bool = False,
                        block_q: int = 8, block_n: int = 128, mesh=None,
                        precision="f32") -> Array:
    """Candidate-compacted batched LC-RWMD db -> query."""
    return lc_act_scores_cand(corpus, Q_ids, Q_w, cand, iters=0,
                              use_kernels=use_kernels, block_q=block_q,
                              block_n=block_n, mesh=mesh,
                              precision=precision)


@functools.partial(jax.jit, static_argnames=_CAND_DIST_STATIC)
def lc_rwmd_scores_rev_cand(corpus: Corpus, Q_ids: Array, Q_w: Array,
                            cand: Array, *, use_kernels: bool = False,
                            block_q: int = 8, block_n: int = 128,
                            block_v: int = 256, mesh=None,
                            precision="f32") -> Array:
    """Candidate-compacted batched LC-RWMD query -> db."""
    Dq = _pin_handoff(_rev_handoff(phase1_stacked_dist(
        corpus.coords, Q_ids, Q_w, precision=precision)))
    return rev_min_cand_blocked(corpus, Dq, Q_w, cand, block_q,
                                use_kernels=use_kernels, block_n=block_n,
                                block_v=block_v, mesh=mesh)


@functools.partial(jax.jit, static_argnames=_CAND_STATIC)
def lc_omr_scores_cand(corpus: Corpus, Q_ids: Array, Q_w: Array,
                       cand: Array, *, use_kernels: bool = False,
                       block_q: int = 8, block_n: int = 128, mesh=None,
                       precision="f32") -> Array:
    """Candidate-compacted batched LC-OMR."""
    Z, W = _pin_handoff(*phase1_batched(corpus.coords, Q_ids, Q_w, 2,
                                        precision=precision))
    return omr_reduce_cand_blocked(corpus, Z, W[..., 0], cand, block_q,
                                   use_kernels=use_kernels, block_n=block_n,
                                   mesh=mesh)


@functools.partial(jax.jit, static_argnames=_CAND_DIST_STATIC)
def lc_ict_scores_cand(corpus: Corpus, Q_ids: Array, Q_w: Array,
                       cand: Array, *, use_kernels: bool = False,
                       block_q: int = 8, block_n: int = 128,
                       block_v: int = 256, mesh=None,
                       precision="f32") -> Array:
    """Candidate-compacted batched LC-ICT (the cascade's tight rescorer)."""
    Dq = _pin_handoff(_rev_handoff(phase1_stacked_dist(
        corpus.coords, Q_ids, Q_w, precision=precision)))
    return ict_reduce_cand_blocked(corpus, Dq, Q_w, cand, block_q,
                                   use_kernels=use_kernels, block_n=block_n,
                                   block_v=block_v, mesh=mesh)
