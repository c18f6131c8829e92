"""Pallas TPU kernels: the cascade's candidate reductions.

The cascade's candidate engines (``core/lc`` ``*_cand_blocked``) score each
query against its own (b,) surviving rows from per-query tables that
Phase 1 hands over. Two paths, split by mode (the wrappers in
``kernels/ops`` pick by the mode, which is static):

* Ladder modes ("pour", "omr"): the table has one row per rung, (rows, v)
  with rows = k + 2 * iters or 3. The wrapper gathers each candidate
  slot's rungs by its vocabulary id with one XLA gather, rung-major with
  the slots on lanes, (nq, rows, b, hp), under ``emd.ladder_gather``; the
  kernel only reduces, on a (query, candidate block) grid, a sublane tile
  of candidate rows at a time. Its cost follows the candidates, not v.
* Distance modes ("rev_min", "ict"): the table has one row per query bin
  (h = 500 at 20NEWS widths), so a gathered copy would be
  (nq, b, hmax, h), 8.6 GB for 8 queries at 1,024 candidates. These keep
  the in-kernel one-hot gather on a (query, candidate block, vocabulary
  slab) grid: each step gathers its candidate block's entries from one
  ``block_v`` slab of the query's table into a VMEM accumulator, and the
  last slab reduces the completed per-row ladders.

One-hot layout. The table is row-major over its rows, the vocabulary on
the 128-wide lane axis. One candidate row's ids (1, hp) broadcast over a
slab's sublanes give the (block_v, hp) one-hot, and the MXU contraction
``table_slab (rows, block_v) @ onehot (block_v, hp)`` lands the row's
gathered ladder as (rows, hp). hp is hmax padded to a lane multiple; pad
slots carry id 0 and weight 0 and contribute exactly 0.

Exactness. Either gather copies the table's values bit for bit. The XLA
gather does by definition. The one-hot runs on the MXU in bfloat16 (0/1
are exact), and a float32 table rides as three bfloat16 parts that sum
back to it exactly (:func:`split_bf16`), each contracted against the same
one-hot with f32 accumulation: every gathered value is 1.0 * part + exact
zeros, at 3 MXU passes where a float32 contraction would take 6.

The reductions use the reference engines' formulas (``lc.pour``,
``lc.ict_pour``, the Algorithm-1 and masked-min expressions). What needs
a sort or a cumulative sum — ICT's cost order, both pours' exclusive
capacity prefix — depends only on the vocabulary row, so the wrappers
compute it once per query over the (v, ...) table with the reference's
own ``jnp`` ops and gather it like any other rung. The conformance
contract (``tests/test_cand_kernels.py``): the gathers are bitwise-exact
and scores match the reference candidate engines to within a few ulps
(the remaining ulps are summation order).

Modes:
  "pour"    — LC-ACT Phase 2/3 (iters >= 1) and the LC-RWMD masked-min
              dump (iters == 0); rows Z | W | prefix(W).
  "omr"     — LC-OMR Algorithm-1 top-2 reduction; rows Z0, Z1, W0.
  "rev_min" — reverse-RWMD masked (min,+) over the distance handoff;
              rows = query bins.
  "ict"     — LC-ICT full-ladder pour; rows = sorted costs | sorted
              capacities | their exclusive prefix | the max-FINITE dump
              cost (never ``lc.PAD_DIST`` — see ``ict_pour``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import matmul_precision, pad_dist_for
from repro.kernels.tiling import SUBLANE, compiler_params, round_up

#: Modes whose table is built from the Phase-1 ranked (Z, W) handoff, one
#: row per rung: gathered by id outside the kernel.
POUR_MODES = ("pour", "omr")
#: Modes whose table is built from the (v, h) distance handoff, one row
#: per query bin: gathered by one-hot inside the kernel.
DIST_MODES = ("rev_min", "ict")
MODES = POUR_MODES + DIST_MODES

#: One-hot table rows of one segment are padded to the bf16 sublane tile,
#: so every segment and bf16 part starts on a tile boundary. Slots (hmax)
#: are padded to ``tiling.LANE``.
ROW_TILE = 16


def split_bf16(table: jax.Array) -> jax.Array:
    """Exact bfloat16 decomposition of a float table: (..., w, v) ->
    (..., P, w, v) with the P parts summing back to ``table`` bit-for-bit
    in float32 (P = 1 for a bfloat16 table, 3 for float32: the top 8
    significand bits, the next 8, and the last 8).

    Each part is cut by clearing the low 16 bits of a float32 word, so it
    is a bfloat16 value exactly and every conversion below is exact. The
    cut is integer arithmetic on purpose: written as ``t - bf16(t)``, a
    compiler that may keep excess precision (XLA on TPU does) folds the
    round trip to ``t`` and the residue parts to zero."""
    if table.dtype == jnp.bfloat16:
        return table[..., None, :, :]

    def top(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)

    t = table.astype(jnp.float32)
    hi = top(t)
    r = t - hi                         # exact: the low 16 significand bits
    mid = top(r)
    return jnp.stack([hi, mid, r - mid], axis=-3).astype(jnp.bfloat16)


def segment_rows(w: int) -> int:
    """Padded row count of a ``w``-row one-hot table segment."""
    return round_up(w, ROW_TILE)


def table_rows(mode: str, *, k: int = 1, iters: int = 0, qh: int = 1) -> int:
    """Rows of one (unsplit) table of ``mode`` — the ladder height per
    candidate entry (see the module docstring). The ladder modes' rows
    are gathered as they are; the one-hot modes' are tile-padded."""
    if mode == "pour":
        return k + 2 * iters
    if mode == "omr":
        return 3
    if mode == "rev_min":
        return segment_rows(qh)
    return 3 * segment_rows(qh) + ROW_TILE                 # "ict"


def gather_slab(tab_ref, ids, parts: int, rows: int, acc):
    """Add one slab's contribution to one candidate row's gathered ladder.

    ``tab_ref``: (1, parts * rows, block_v) bf16 slab (part-major);
    ``ids``: (1, hp) entry ids relative to the slab start; ``acc``:
    (rows, hp) float32 running gather. Entries whose id falls in the slab
    gain their table rows part by part, all others exact zeros; an entry
    hits exactly one slab, so it accumulates 0 + its parts = its value."""
    block_v = tab_ref.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, (block_v, ids.shape[-1]), 0)
    onehot = (col == ids).astype(jnp.float32).astype(jnp.bfloat16)
    for p in range(parts):
        acc = acc + jax.lax.dot_general(
            tab_ref[0, p * rows:(p + 1) * rows, :], onehot,
            (((1,), (0,)), ((), ())),
            precision=matmul_precision(jnp.bfloat16),
            preferred_element_type=jnp.float32)
    return acc


def _sum_lanes(e):
    return jnp.sum(e, axis=-1, keepdims=True)


def _reduce_pour(g, x, *, k: int, iters: int):
    """``lc.pour`` on gathered ladders: rows Z[0:k], W[k:k+iters],
    exclusive prefix of W[k+iters:k+2*iters]."""
    if iters == 0:
        return _sum_lanes(x * g[0:1])
    poured = None
    taken = None
    for l in range(iters):
        w = g[k + l:k + l + 1]
        pre = g[k + iters + l:k + iters + l + 1]
        r = jnp.clip(x - pre, 0.0, w)
        rz = r * g[l:l + 1]
        poured = rz if poured is None else poured + rz
        taken = r if taken is None else taken + r
    remainder = jnp.maximum(x - taken, 0.0)
    return _sum_lanes(poured) + _sum_lanes(remainder * g[iters:iters + 1])


def _reduce_omr(g, x):
    """Algorithm 1 on rows Z0, Z1, W0."""
    z0, z1, w0 = g[0:1], g[1:2], g[2:3]
    rest = x - jnp.minimum(x, w0)
    return _sum_lanes(jnp.where(z0 == 0.0, rest * z1, x * z0))


def _reduce_rev_min(g, x, qw):
    """Masked (min,+) . q_w on rows = query bins; ``qw`` (rows, 1).
    Invalid slots mask to the f32 pad sentinel (reduced-precision
    sentinels upcast to >= it, so every comparison stays strict)."""
    big = pad_dist_for(jnp.float32)
    cmin = jnp.min(jnp.where(x > 0.0, g, big), axis=1, keepdims=True)
    return jnp.sum(cmin * qw, axis=0, keepdims=True)


def _reduce_ict(g, x, *, hs: int):
    """``lc.ict_pour`` on rows sorted costs | sorted capacities |
    exclusive capacity prefix (``hs`` rows each) | dump cost."""
    cost, cap, pre = g[0:hs], g[hs:2 * hs], g[2 * hs:3 * hs]
    dump = g[3 * hs:3 * hs + 1]
    r = jnp.clip(x - pre, 0.0, cap)                           # (hs, hp)
    poured = jnp.sum(r * cost, axis=0, keepdims=True)         # (1, hp)
    remainder = jnp.maximum(x - jnp.sum(r, axis=0, keepdims=True), 0.0)
    return _sum_lanes(poured + remainder * dump)


def _ladder_kernel(g_ref, x_ref, t_ref, *, mode: str, k: int, iters: int):
    """Grid = (nq, cand_blocks). Reduces the block's gathered
    (rows, bb, hp) ladders a sublane tile of candidate rows at a time;
    each rung is loaded from VMEM where the reduction uses it."""
    bb = x_ref.shape[1]
    sub = SUBLANE if bb % SUBLANE == 0 else bb

    def reduce(i, carry):
        rows = pl.ds(pl.multiple_of(i * sub, sub), sub)
        g = g_ref.at[0, :, rows, :]                          # (rows, sub, hp)
        x = x_ref[0, rows, :].astype(jnp.float32)            # (sub, hp)
        if mode == "pour":
            t = _reduce_pour(g, x, k=k, iters=iters)
        else:
            t = _reduce_omr(g, x)
        t_ref[:, rows, :] = t                                # (1, sub, 1)
        return carry

    jax.lax.fori_loop(0, bb // sub, reduce, 0)


def _onehot_kernel(*refs, mode: str, parts: int, rows: int, hs: int):
    """Grid = (nq, cand_blocks, vocab_slabs); the slab axis is innermost.
    Each step adds its slab's one-hot gather into the persistent
    (bb, rows, hp) accumulator; the last slab reduces row by row."""
    ids_ref, x_ref, tab_ref = refs[:3]
    qw_ref = refs[3] if mode == "rev_min" else None
    t_ref, acc_ref = refs[-2:]
    u = pl.program_id(2)
    bb = ids_ref.shape[1]
    block_v = tab_ref.shape[-1]

    @pl.when(u == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def gather(i, carry):
        ids = ids_ref[0, pl.ds(i, 1), :] - u * block_v           # (1, hp)
        acc_ref[i] = gather_slab(tab_ref, ids, parts, rows, acc_ref[i])
        return carry

    jax.lax.fori_loop(0, bb, gather, 0)

    @pl.when(u == pl.num_programs(2) - 1)
    def _reduce():
        def reduce(i, carry):
            g = acc_ref[i]                                       # (rows, hp)
            x = x_ref[0, pl.ds(i, 1), :].astype(jnp.float32)     # (1, hp)
            if mode == "rev_min":
                t = _reduce_rev_min(g, x, qw_ref[0].astype(jnp.float32))
            else:
                t = _reduce_ict(g, x, hs=hs)
            t_ref[0, pl.ds(i, 1), :] = t
            return carry

        jax.lax.fori_loop(0, bb, reduce, 0)


@functools.partial(jax.jit, static_argnames=("mode", "k", "iters", "qh",
                                             "block_n", "block_v",
                                             "interpret"))
def cand_pallas(xg: jax.Array, table: jax.Array,
                idsg: jax.Array | None = None, qw: jax.Array | None = None,
                *, mode: str, k: int = 1, iters: int = 0, qh: int = 1,
                block_n: int = 128, block_v: int = 256,
                interpret: bool = False) -> jax.Array:
    """Candidate reduction over a query batch.

    Args:
      xg:    (nq, b, hp) residual weights of each query's candidate rows
             (``corpus.w[cand]``, slots padded to a lane multiple;
             padding slots/rows carry weight 0).
      table: ladder modes — (nq, rows, b, hp) float32 ladders gathered at
             each candidate slot, rung-major (``rows`` =
             :func:`table_rows` of the mode); one-hot modes — the
             (nq, P * rows, vp) bfloat16 parts of the per-query table
             (:func:`split_bf16`, part-major).
      idsg:  (nq, b, hp) int32 vocabulary ids of the candidate slots
             (``corpus.ids[cand]``, padding id 0); one-hot modes only.
      qw:    (nq, rows, 1) query weights per bin ("rev_min" only; 0 at
             padded bins).
    Returns t: (nq, b, 1) scores at the candidate rows.
    Caller guarantees b % block_n == 0, and vp % block_v == 0 for the
    one-hot modes (ops.py).
    """
    assert mode in MODES, mode
    nq, b, hp = xg.shape
    assert b % block_n == 0, (xg.shape, block_n)
    rows = table_rows(mode, k=k, iters=iters, qh=qh)
    if mode in POUR_MODES:
        assert table.shape == (nq, rows, b, hp), (table.shape, rows)
        return pl.pallas_call(
            functools.partial(_ladder_kernel, mode=mode, k=k, iters=iters),
            grid=(nq, b // block_n),
            in_specs=[
                pl.BlockSpec((1, rows, block_n, hp),
                             lambda q, i: (q, 0, i, 0)),
                pl.BlockSpec((1, block_n, hp), lambda q, i: (q, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_n, 1), lambda q, i: (q, i, 0)),
            out_shape=jax.ShapeDtypeStruct((nq, b, 1), jnp.float32),
            compiler_params=compiler_params("parallel", "parallel"),
            interpret=interpret,
        )(table, xg)
    assert idsg.shape == xg.shape, (idsg.shape, xg.shape)
    vp = table.shape[-1]
    parts = table.shape[1] // rows
    assert table.shape[1] == parts * rows and vp % block_v == 0, table.shape
    kernel = functools.partial(_onehot_kernel, mode=mode, parts=parts,
                               rows=rows, hs=segment_rows(qh))
    in_specs = [
        pl.BlockSpec((1, block_n, hp), lambda q, i, u: (q, i, 0)),
        pl.BlockSpec((1, block_n, hp), lambda q, i, u: (q, i, 0)),
        pl.BlockSpec((1, parts * rows, block_v), lambda q, i, u: (q, 0, u)),
    ]
    args = [idsg, xg, table]
    if mode == "rev_min":
        assert qw.shape == (nq, rows, 1), (qw.shape, rows)
        in_specs.append(pl.BlockSpec((1, rows, 1), lambda q, i, u: (q, 0, 0)))
        args.append(qw)
    return pl.pallas_call(
        kernel,
        grid=(nq, b // block_n, vp // block_v),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_n, 1), lambda q, i, u: (q, i, 0)),
        out_shape=jax.ShapeDtypeStruct((nq, b, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_n, rows, hp), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel",
                                        "arbitrary"),
        interpret=interpret,
    )(*args)
