"""Jitted public wrappers around the Pallas kernels.

Handle padding to block multiples, backend selection and shape
restoration. The kernels compile with Mosaic on a TPU; on the CPU
platform (the test suites) they run in Pallas interpret mode. Any other
platform compiles too — and fails loudly if it cannot — rather than
silently interpreting.

``JAX_PALLAS_INTERPRET=1`` forces interpret mode on every backend — the
CI kernel-conformance job sets it so the suite pins the interpreted
semantics explicitly rather than relying on backend detection.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp

from repro.core import scopes
from repro.core.precision import pad_dist_for
from repro.kernels.cand_pour import (DIST_MODES, POUR_MODES, cand_pallas,
                                     segment_rows, split_bf16, table_rows)
from repro.kernels import tiling
from repro.kernels.act_phase2 import act_phase2_pallas
from repro.kernels.dist_topk import dist_topk_pallas


#: Read once at import: the flag participates in no jit cache key, so a
#: mid-process change could not take effect anyway (the first trace's
#: choice would be reused) — pinning it at import makes that explicit.
_FORCE_INTERPRET = os.environ.get("JAX_PALLAS_INTERPRET", "") not in ("",
                                                                      "0")


def _interpret_default() -> bool:
    return _FORCE_INTERPRET or jax.default_backend() == "cpu"


_round_up = tiling.round_up


@functools.partial(jax.jit, static_argnames=("k", "block_v", "block_h",
                                             "out_dtype"))
def dist_topk_batched(coords: jax.Array, qcs: jax.Array, k: int, *,
                      qmask: jax.Array | None = None,
                      block_v: int = 256, block_h: int = 256,
                      out_dtype: str = "float32"):
    """Fused distance + row-top-k for a query batch in one kernel launch.

    coords (v, m), qcs (nq, h, m) -> Z, S (nq, v, k).
    ``qmask``: optional (nq, h) validity mask (1 = real query bin);
    padding columns added here for blocking are always masked out.
    ``out_dtype``: storage dtype of the Z ladder (a precision policy's
    storage role); selection always runs in float32 inside the kernel.
    """
    v, m = coords.shape
    nq, h, _ = qcs.shape
    block_v = min(block_v, _round_up(v, 8))
    block_h = min(block_h, _round_up(h, 8))
    vp = _round_up(v, block_v)
    hp = _round_up(h, block_h)
    mask = (jnp.ones((nq, h), jnp.float32) if qmask is None
            else qmask.astype(jnp.float32))
    mask = jnp.pad(mask, ((0, 0), (0, hp - h))).reshape(nq, 1, hp)
    coords_p = jnp.pad(coords, ((0, vp - v), (0, 0)))
    qcs_p = jnp.pad(qcs, ((0, 0), (0, hp - h), (0, 0)))
    z, s = dist_topk_pallas(coords_p, qcs_p, mask, k, block_v=block_v,
                            block_h=block_h, interpret=_interpret_default(),
                            out_dtype=out_dtype)
    return z[:, :v], s[:, :v]


@functools.partial(jax.jit, static_argnames=("k", "block_v", "block_h",
                                             "out_dtype"))
def dist_topk(coords: jax.Array, qc: jax.Array, k: int, *,
              qmask: jax.Array | None = None,
              block_v: int = 256, block_h: int = 256,
              out_dtype: str = "float32"):
    """Fused distance + row-top-k. coords (v, m), qc (h, m) -> Z, S (v, k).

    Single-query view of ``dist_topk_batched`` (query-batch grid of 1).
    ``qmask``: optional (h,) validity mask (1 = real query bin).
    """
    z, s = dist_topk_batched(coords, qc[None], k,
                             qmask=None if qmask is None else qmask[None],
                             block_v=block_v, block_h=block_h,
                             out_dtype=out_dtype)
    return z[0], s[0]


@functools.partial(jax.jit, static_argnames=("block_n", "block_h"))
def act_phase2_batched(x: jax.Array, zg: jax.Array, wg: jax.Array, *,
                       block_n: int = 256, block_h: int = 256) -> jax.Array:
    """Fused Phase-2/3 pour for a query batch in one kernel launch.

    x (n, hmax) shared residual weights; zg (nq, k, n, hmax) and
    wg (nq, k-1, n, hmax) per-query rung-major ladders -> t (nq, n).
    Padding rows/slots must carry zero weight (they do, by the Corpus
    construction), so block padding contributes exactly 0 cost."""
    n, hmax = x.shape
    block_n = min(block_n, _round_up(n, 8))
    block_h = min(block_h, _round_up(hmax, 8))
    np_, hp = _round_up(n, block_n), _round_up(hmax, block_h)
    pad2 = ((0, np_ - n), (0, hp - hmax))
    pad4 = ((0, 0), (0, 0)) + pad2
    t = act_phase2_pallas(jnp.pad(x, pad2), jnp.pad(zg, pad4),
                          jnp.pad(wg, pad4), block_n=block_n,
                          block_h=block_h, interpret=_interpret_default())
    return t[:, :n, 0]


@functools.partial(jax.jit, static_argnames=("block_n", "block_h"))
def act_phase2(x: jax.Array, zg: jax.Array, wg: jax.Array, *,
               block_n: int = 256, block_h: int = 256) -> jax.Array:
    """Fused Phase-2/3 pour. x (n, hmax), zg (k, n, hmax),
    wg (k-1, n, hmax) -> t (n,). Single-query view of
    ``act_phase2_batched``."""
    return act_phase2_batched(x, zg[None], wg[None], block_n=block_n,
                              block_h=block_h)[0]


# ------------------------------------------------------ candidate kernels
#
# Per-query candidate reductions (cascade stages), ``kernels/cand_pour``.
# Shapes: idsg/xg (nq, b, hmax) are the candidate sub-corpus
# (corpus.ids[cand] / corpus.w[cand]); the Phase-1 handoff rides in
# per-query tables laid out row-major (rows, v). The ladder modes (pour,
# omr) gather each slot's rungs here by id and hand the kernel the
# gathered ladders; the one-hot modes (rev_min, ict) hand it the table's
# exact bfloat16 parts and gather in-kernel. Padding added here
# (candidate rows to a block_n multiple, slots to a lane multiple, and for
# the one-hot modes table rows to a tile and the vocabulary to a block_v
# multiple) contributes exactly zero cost and is sliced off.

#: Largest gathered ladder (nq, rows, b, hp) float32 one launch
#: materialises. Beyond it the candidate axis is gathered and reduced in
#: chunks of whole candidate blocks, so the ladder stays bounded however
#: large the budget.
GATHER_CAP_BYTES = 2**30


def _gather_chunk(nq: int, rows: int, bp: int, hp: int, block_n: int) -> int:
    """Candidate rows per gather: all ``bp`` if their ladder fits
    :data:`GATHER_CAP_BYTES`, else the most whole ``block_n`` blocks that
    do (at least one)."""
    per_row = nq * rows * hp * 4
    if bp * per_row <= GATHER_CAP_BYTES:
        return bp
    return max(1, GATHER_CAP_BYTES // (per_row * block_n)) * block_n


def _pad_cands(idsg, xg, block_n: int):
    """Pad the candidate rows to a ``block_n`` multiple (``block_n``
    clamped to the rows) and the slots to a lane multiple; pads carry id
    0 and weight 0. Returns the padded pair and the row tile."""
    _, b, hmax = idsg.shape
    block_n = min(block_n, _round_up(b, 8))
    pad = ((0, 0), (0, _round_up(b, block_n) - b),
           (0, _round_up(hmax, tiling.LANE) - hmax))
    return jnp.pad(idsg, pad), jnp.pad(xg, pad), block_n


def _ladder_launch(idsg, xg, table, mode: str, *, k: int = 1,
                   iters: int = 0, block_n: int):
    """Gather each candidate slot's rungs by its id and reduce them.
    ``table``: (nq, rows, v) row-major per-query table, one row per
    rung."""
    b = idsg.shape[1]
    idsg, xg, block_n = _pad_cands(idsg, xg, block_n)
    nq, bp, hp = idsg.shape
    table = table.astype(jnp.float32)
    interpret = _interpret_default()

    def launch(ids, x):
        with jax.named_scope(scopes.LADDER_GATHER):
            g = jax.vmap(lambda t, i: t[:, i])(table, ids)  # (nq, rows, c, hp)
        return cand_pallas(x, g, mode=mode, k=k, iters=iters,
                           block_n=block_n, interpret=interpret)

    chunk = _gather_chunk(nq, table.shape[1], bp, hp, block_n)
    if chunk == bp:
        return launch(idsg, xg)[:, :b, 0]
    nc = -(-bp // chunk)
    pad = ((0, 0), (0, nc * chunk - bp), (0, 0))

    def chunks(a):                              # -> (nc, nq, chunk, hp)
        return jnp.swapaxes(jnp.pad(a, pad).reshape(nq, nc, chunk, hp), 0, 1)
    t = jax.lax.map(lambda c: launch(*c), (chunks(idsg), chunks(xg)))
    return jnp.swapaxes(t, 0, 1).reshape(nq, nc * chunk)[:, :b]


def _onehot_launch(idsg, xg, table, mode: str, *, qh: int, block_n: int,
                   block_v: int, qw=None):
    """Pad, split and launch one one-hot candidate kernel. ``table``:
    (nq, w, v) row-major per-query table (w <= the mode's rows)."""
    nq, b, _ = idsg.shape
    idsg, xg, block_n = _pad_cands(idsg, xg, block_n)
    v = table.shape[-1]
    rows = table_rows(mode, qh=qh)
    block_v = min(block_v, _round_up(v, 8))
    vp = _round_up(v, block_v)
    table = jnp.pad(table, ((0, 0), (0, rows - table.shape[1]), (0, vp - v)))
    parts = split_bf16(table).reshape(nq, -1, vp)
    if qw is not None:
        qw = jnp.pad(qw.astype(jnp.float32),
                     ((0, 0), (0, rows - qh)))[..., None]
    t = cand_pallas(xg, parts, idsg, qw, mode=mode, qh=qh, block_n=block_n,
                    block_v=block_v, interpret=_interpret_default())
    return t[:, :b, 0]


def _rows(*cols):
    """(nq, v) columns -> the (nq, len(cols), v) row-major table."""
    return jnp.stack(cols, axis=1)


@functools.partial(jax.jit, static_argnames=("iters", "block_n"))
def cand_pour(idsg: jax.Array, xg: jax.Array, Z: jax.Array,
              W: jax.Array | None, iters: int, *,
              block_n: int = 128) -> jax.Array:
    """Candidate gather + pour: the LC-ACT (iters >= 1) and LC-RWMD
    masked-min (iters == 0) candidate reductions.

    idsg/xg (nq, b, hmax); Z (nq, v, >= iters+1) cost ladder;
    W (nq, v, >= iters) capacity ladder (``None`` when iters == 0)
    -> (nq, b) scores, matching the reference candidate engines to
    within a few ulps (exact gather + the reference reduction formulas;
    see ``kernels/cand_pour``'s conformance notes).
    """
    k = iters + 1
    if iters == 0:
        return _ladder_launch(idsg, xg, _rows(Z[..., 0]), "pour",
                              block_n=block_n)
    # the pour's capacity prefix, taken per vocabulary row exactly as
    # ``lc.pour`` takes it per gathered entry (f32 accumulator)
    Wf = W[..., :iters].astype(jnp.float32)
    prefix = jnp.cumsum(Wf, axis=-1) - Wf
    table = jnp.concatenate([Z[..., :k].astype(jnp.float32), Wf, prefix],
                            axis=-1)
    return _ladder_launch(idsg, xg, jnp.swapaxes(table, 1, 2), "pour", k=k,
                          iters=iters, block_n=block_n)


@functools.partial(jax.jit, static_argnames=("block_n",))
def cand_omr(idsg: jax.Array, xg: jax.Array, Z: jax.Array, W0: jax.Array,
             *, block_n: int = 128) -> jax.Array:
    """Candidate gather + LC-OMR Algorithm-1 reduction.

    idsg/xg (nq, b, hmax); Z (nq, v, 2) top-2 costs; W0 (nq, v) first
    capacities -> (nq, b) scores.
    """
    table = _rows(Z[..., 0], Z[..., 1], W0.astype(Z.dtype))
    return _ladder_launch(idsg, xg, table, "omr", block_n=block_n)


@functools.partial(jax.jit, static_argnames=("block_n", "block_v"))
def cand_rev_min(idsg: jax.Array, xg: jax.Array, Dq: jax.Array,
                 qw: jax.Array, *, block_n: int = 128,
                 block_v: int = 256) -> jax.Array:
    """Fused candidate gather + reverse-RWMD masked (min,+) reduction.

    idsg/xg (nq, b, hmax); Dq (nq, v, h) distance handoff; qw (nq, h)
    query weights -> (nq, b) scores (invalid slots mask to the finite
    ``lc.PAD_DIST``, matching ``lc.rev_min_cand_blocked``).
    """
    h = Dq.shape[-1]
    return _onehot_launch(idsg, xg, jnp.swapaxes(Dq, 1, 2), "rev_min",
                          qw=qw, qh=h, block_n=block_n, block_v=block_v)


@functools.partial(jax.jit, static_argnames=("block_n", "block_v"))
def cand_ict(idsg: jax.Array, xg: jax.Array, Dq: jax.Array,
             qw: jax.Array, *, block_n: int = 128,
             block_v: int = 256) -> jax.Array:
    """Fused candidate gather + LC-ICT full-ladder pour (Algorithm 2).

    idsg/xg (nq, b, hmax); Dq (nq, v, h); qw (nq, h) -> (nq, b) scores.
    The cost order, capacity prefix and dump cost of ``lc.ict_pour``
    depend only on the vocabulary row, so they are taken here once per
    (query, row) with ``ict_pour``'s own ops and gathered in-kernel. The
    remainder dump stays at the max FINITE gathered cost — never
    ``lc.PAD_DIST``, where a ~1e-7 cumsum residue would explode to ~1e23.
    """
    h = Dq.shape[-1]
    C = Dq.astype(jnp.float32)
    order = jnp.argsort(C, axis=-1)
    cost = jnp.take_along_axis(C, order, axis=-1)
    cap = jnp.take_along_axis(
        jnp.broadcast_to(qw.astype(jnp.float32)[:, None, :], C.shape),
        order, axis=-1)
    prefix = jnp.cumsum(cap, axis=-1) - cap
    dump = jnp.max(jnp.where(C < pad_dist_for(jnp.float32), C, 0.0), axis=-1)
    hs = segment_rows(h)

    def seg(a):                                          # (nq, v, h) -> rows
        return jnp.pad(jnp.swapaxes(a, 1, 2), ((0, 0), (0, hs - h), (0, 0)))
    table = jnp.concatenate([seg(cost), seg(cap), seg(prefix),
                             dump[:, None, :]], axis=1)
    return _onehot_launch(idsg, xg, table, "ict", qh=h, block_n=block_n,
                          block_v=block_v)


# --------------------------------------------------- static block metadata
#
# The per-grid-cell block layout of every kernel family, as DATA: the same
# clamp/pad arithmetic the wrappers above apply, but evaluated without
# tracing anything. ``repro.analysis.vmem`` turns these layouts into a
# static VMEM-footprint model (checked in CI, swept by the future tile
# autotuner), so any change to a wrapper's blocking MUST be mirrored here
# — the conformance test pins the two against each other on the padded
# shapes the wrappers actually launch.

_DTYPE_BYTES = {"float32": 4, "int32": 4, "bfloat16": 2, "float16": 2,
                "int8": 1, "uint8": 1, "bool": 1}


@dataclasses.dataclass(frozen=True)
class BlockBuffer:
    """One VMEM-resident buffer of a kernel grid cell.

    role: ``in`` / ``out`` blocks are pipelined by Pallas (double-buffered
    while the grid streams, so they count twice in the footprint) and
    carry the ``array`` they tile, against which Mosaic's block rule is
    checked; ``scratch`` covers the kernel body's dominant temporaries
    (single copy). The scratch entries are a documented lower-ish bound —
    Mosaic may materialize more registers.
    """
    name: str
    shape: tuple[int, ...]
    dtype: str = "float32"
    role: str = "in"
    array: tuple[int, ...] | None = None

    def __post_init__(self) -> None:
        assert self.role in ("in", "out", "scratch"), self.role
        assert self.dtype in _DTYPE_BYTES, self.dtype
        assert (self.array is None) == (self.role == "scratch"), self.name

    @property
    def nbytes(self) -> int:
        """Tile-padded VMEM bytes (``tiling.padded_bytes``)."""
        return tiling.padded_bytes(self.shape, _DTYPE_BYTES[self.dtype])

    @property
    def tiling_error(self) -> str | None:
        """Why Mosaic would refuse this block (None for scratch / legal)."""
        if self.array is None:
            return None
        return tiling.block_tiling_error(self.shape, self.array)


@dataclasses.dataclass(frozen=True)
class KernelBlocks:
    """Static description of one kernel launch: grid + per-cell buffers."""
    family: str
    grid: tuple[int, ...]
    buffers: tuple[BlockBuffer, ...]

    def vmem_bytes(self, *, pipeline_depth: int = 2) -> int:
        """Per-core VMEM footprint of one grid cell: pipelined in/out
        blocks count ``pipeline_depth`` times (Pallas double-buffers the
        HBM<->VMEM streams by default), scratch once."""
        total = 0
        for b in self.buffers:
            total += b.nbytes * (1 if b.role == "scratch" else pipeline_depth)
        return total

    def buffer(self, name: str) -> BlockBuffer:
        for b in self.buffers:
            if b.name == name:
                return b
        raise KeyError(f"{self.family} has no buffer {name!r}; "
                       f"have {[b.name for b in self.buffers]}")


def _positive(**dims) -> None:
    bad = {k: v for k, v in dims.items() if v < 1}
    if bad:
        raise ValueError(f"kernel dims/blocks must be >= 1, got {bad}")


def _dist_topk_layout(*, nq: int, v: int, h: int, m: int, k: int,
                      block_v: int = 256, block_h: int = 256,
                      dtype: str = "float32") -> KernelBlocks:
    _positive(nq=nq, v=v, h=h, m=m, k=k, block_v=block_v, block_h=block_h)
    block_v = min(block_v, _round_up(v, 8))
    block_h = min(block_h, _round_up(h, 8))
    vp, hp = _round_up(v, block_v), _round_up(h, block_h)
    return KernelBlocks(
        family="dist_topk",
        grid=(nq, vp // block_v, hp // block_h),
        buffers=(
            BlockBuffer("coords", (block_v, m), array=(vp, m)),
            BlockBuffer("qcs", (1, block_h, m), array=(nq, hp, m)),
            BlockBuffer("qmask", (1, 1, block_h), array=(nq, 1, hp)),
            # z is the Z-ladder STORAGE block (``dtype`` = the precision
            # policy's storage role — the axis that shrinks under bf16)
            BlockBuffer("z", (1, block_v, k), dtype, "out", (nq, vp, k)),
            BlockBuffer("s", (1, block_v, k), "int32", "out", (nq, vp, k)),
            # the (bv, bh) distance tile + its global column ids — the
            # body's working set that never leaves VMEM
            BlockBuffer("dist_tile", (block_v, block_h), role="scratch"),
            BlockBuffer("col_ids", (block_v, block_h), "int32", "scratch"),
        ))


def _act_phase2_layout(*, nq: int, n: int, h: int, iters: int,
                       block_n: int = 256, block_h: int = 256,
                       dtype: str = "float32") -> KernelBlocks:
    _positive(nq=nq, n=n, h=h, block_n=block_n, block_h=block_h)
    if iters < 0:
        raise ValueError(f"iters must be >= 0, got {iters}")
    block_n = min(block_n, _round_up(n, 8))
    block_h = min(block_h, _round_up(h, 8))
    np_, hp = _round_up(n, block_n), _round_up(h, block_h)
    return KernelBlocks(
        family="act_phase2",
        grid=(nq, np_ // block_n, hp // block_h),
        buffers=(
            BlockBuffer("x", (block_n, block_h), array=(np_, hp)),
            # the gathered rung-major Phase-1 ladders ride in storage
            # dtype; the pour itself upcasts rung-by-rung to float32
            BlockBuffer("zg", (1, iters + 1, block_n, block_h), dtype,
                        array=(nq, iters + 1, np_, hp)),
            BlockBuffer("wg", (1, iters, block_n, block_h), dtype,
                        array=(nq, iters, np_, hp)),
            BlockBuffer("t", (1, block_n, 1), role="out",
                        array=(nq, np_, 1)),
            # pour temporaries: acc / prefix / poured / r, each (bn, bh)
            BlockBuffer("pour_tmp", (4, block_n, block_h), role="scratch"),
        ))


def _cand_pour_layout(*, nq: int, b: int, h: int, v: int, k: int,
                      iters: int, mode: str = "pour", block_n: int = 128,
                      dtype: str = "float32") -> KernelBlocks:
    """The ladder modes' reduction grid (``kernels/cand_pour``): each step
    reads one candidate block of gathered ladders. They are float32
    whatever the storage ``dtype`` (the wrapper upcasts the table before
    it gathers), and no buffer holds the vocabulary ``v``."""
    assert mode in POUR_MODES, mode
    _positive(nq=nq, b=b, h=h, v=v, k=k, block_n=block_n)
    if iters < 0:
        raise ValueError(f"iters must be >= 0, got {iters}")
    rows = table_rows(mode, k=k, iters=iters)
    block_n = min(block_n, _round_up(b, 8))
    bp = _round_up(b, block_n)
    hp = _round_up(h, tiling.LANE)
    return KernelBlocks(
        family="cand_pour",
        grid=(nq, bp // block_n),
        buffers=(
            BlockBuffer("ladders", (1, rows, block_n, hp),
                        array=(nq, rows, bp, hp)),
            BlockBuffer("xg", (1, block_n, hp), array=(nq, bp, hp)),
            BlockBuffer("t", (1, block_n, 1), role="out",
                        array=(nq, bp, 1)),
            # one sublane tile of candidate rows: the rung in use and the
            # pour's running sums
            BlockBuffer("reduce_tmp", (4, tiling.SUBLANE, hp),
                        role="scratch"),
        ))


def _cand_dist_layout(*, nq: int, b: int, h: int, v: int, qh: int,
                      mode: str = "rev_min", block_n: int = 128,
                      block_v: int = 256,
                      dtype: str = "float32") -> KernelBlocks:
    """The one-hot modes' gather-accumulate grid (``kernels/cand_pour``).
    The table rides as exact bf16 parts: one for a bf16 storage table,
    three for float32 (ICT's ladders are always float32)."""
    assert mode in DIST_MODES, mode
    _positive(nq=nq, b=b, h=h, v=v, qh=qh, block_n=block_n,
              block_v=block_v)
    rows = table_rows(mode, qh=qh)
    f32_table = dtype != "bfloat16" or mode == "ict"
    tab_rows = (3 if f32_table else 1) * rows
    block_n = min(block_n, _round_up(b, 8))
    block_v = min(block_v, _round_up(v, 8))
    bp, vp = _round_up(b, block_n), _round_up(v, block_v)
    hp = _round_up(h, tiling.LANE)
    reduce_tmp = {"rev_min": (rows, hp),
                  "ict": (3, segment_rows(qh), hp)}[mode]
    qw = ((BlockBuffer("qw", (1, rows, 1), array=(nq, rows, 1)),)
          if mode == "rev_min" else ())
    return KernelBlocks(
        family="cand_dist",
        grid=(nq, bp // block_n, vp // block_v),
        buffers=(
            BlockBuffer("idsg", (1, block_n, hp), "int32",
                        array=(nq, bp, hp)),
            BlockBuffer("xg", (1, block_n, hp), array=(nq, bp, hp)),
            # one streamed vocabulary slab of the query's table per step
            BlockBuffer("table", (1, tab_rows, block_v), "bfloat16",
                        array=(nq, tab_rows, vp)),
            *qw,
            BlockBuffer("t", (1, block_n, 1), role="out",
                        array=(nq, bp, 1)),
            # the persistent per-row gather accumulator
            BlockBuffer("acc", (block_n, rows, hp), role="scratch"),
            BlockBuffer("onehot", (block_v, hp), "bfloat16", "scratch"),
            BlockBuffer("slab", (rows, hp), role="scratch"),
            BlockBuffer("reduce_tmp", reduce_tmp, role="scratch"),
        ))


#: family name -> layout function. The enumerable surface
#: ``repro.analysis.vmem`` iterates; every pallas_call in this package
#: belongs to exactly one family (``cand_pour`` covers modes pour/omr,
#: ``cand_dist`` modes rev_min/ict via the ``mode`` kwarg).
KERNEL_FAMILIES = {
    "dist_topk": _dist_topk_layout,
    "act_phase2": _act_phase2_layout,
    "cand_pour": _cand_pour_layout,
    "cand_dist": _cand_dist_layout,
}


def block_layout(family: str, **dims) -> KernelBlocks:
    """Static per-cell block layout of one kernel launch (see
    :data:`KERNEL_FAMILIES` for the per-family dim kwargs)."""
    if family not in KERNEL_FAMILIES:
        raise ValueError(f"unknown kernel family {family!r}; "
                         f"one of {sorted(KERNEL_FAMILIES)}")
    return KERNEL_FAMILIES[family](**dims)
