"""The cascade driver: prune with cheap bounds, rescore the survivors.

One search is a ladder of ``(method, budget)`` stages (``CascadeSpec``):
stage 1 scores the FULL corpus through the registry's batched multi-query
engine — or, when the spec names a sublinear candidate source
(``repro.candidates``), only the rows the built source emits, which is
what breaks the O(n) stage-1 wall — and keeps its ``budget`` best rows
per query; every later stage
scores only the surviving candidate set through the method's
candidate-compacted engine (``retrieval.cand_scores`` — Phase 1 unchanged,
Phase 2/3 gather-compacted to a ``(nq, budget)`` sub-corpus); the final
rescorer ranks the last survivors and the top-l comes from ITS scores,
mapped back to global row ids.

The whole ladder jits into one program when the rescorer is jittable
(every registry method, ``sinkhorn``); the exact-``emd`` rescorer prunes
on device and rescores on the host. ``topk_blocks`` selects the
shard-blocked top-budget used by the distributed step: per-block local
top-k (each block = one model shard's columns) followed by a ladder merge
of the small winner tensors — the full (nq, n) score matrix is never
gathered across the mesh. Tie-breaking caveat: the merged selection
resolves equal scores by (block, local rank) rather than the plain
``lax.top_k`` global-lowest-index rule, so exactly-tied boundary rows may
swap between equally-valid candidate sets.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cascade import rescore
from repro.cascade.spec import CascadeSpec, resolve_spec
from repro.core import lc, retrieval, scopes
from repro.sharding import annotate

Array = jax.Array

_KNOBS = ("use_kernels", "block_v", "block_h", "block_n", "rev_block",
          "block_q", "mesh", "precision")


class CascadeResult(NamedTuple):
    """Top-l outcome of one cascaded search (ascending rescorer scores and
    the matching global database row ids, (nq, top_l) each)."""
    scores: Array
    indices: Array


#: Largest per-block budget the shard-blocked top-k unrolls.
SHARD_TOPK_MAX = 128


def topk_smallest(scores: Array, k: int, blocks: int = 1):
    """(values, indices) of the k smallest entries per row, ascending.

    ``blocks > 1`` runs the shard-blocked schedule (the distributed
    step's ladder merge): per-block local top-k, then one merge over the
    ``blocks * min(k, n/blocks)`` winners. Exact for any block count —
    a block can hold at most min(k, n/blocks) of the true top-k — and
    falls back to plain ``lax.top_k`` when n does not split evenly, and
    when a block keeps more than ``SHARD_TOPK_MAX`` rows: the extraction
    unrolls one round per kept row (a 40% budget over 4 shards of
    20News is ~4,700 rounds, minutes of compiling), and a block that
    keeps all its rows selects nothing at all.

    The per-block selection is ``lc.streaming_smallest_k``, NOT
    ``lax.top_k``: top_k lowers to a sort/TopK custom call the SPMD
    partitioner cannot shard, so on the mesh it all-gathers the whole
    (nq, blocks, n/blocks) score tensor over "model" before selecting —
    exactly the corpus-scaled traffic this schedule exists to avoid (the
    static collective checker's scaling guard caught it). The streaming
    form is built from min/where/iota, which partitions shard-locally,
    and makes the same selection (ascending, ties to the lowest column).
    """
    n = scores.shape[-1]
    per = n // blocks
    if blocks > 1 and n % blocks == 0 and min(k, per) <= SHARD_TOPK_MAX:
        kb = min(k, per)
        s = annotate.emd_shard_topk(
            scores.reshape(scores.shape[:-1] + (blocks, per)))
        zv, li = lc.streaming_smallest_k(s, kb)      # shard-local top-k
        negv = -zv
        gi = li + (jnp.arange(blocks, dtype=jnp.int32) * per)[:, None]
        negv = annotate.emd_ladder(
            negv.reshape(scores.shape[:-1] + (blocks * kb,)))
        gi = annotate.emd_ladder(
            gi.reshape(scores.shape[:-1] + (blocks * kb,)))
        neg, pos = jax.lax.top_k(negv, k)            # ladder merge
        return -neg, jnp.take_along_axis(gi, pos, axis=-1)
    neg, idx = jax.lax.top_k(-scores, k)
    return -neg, idx


def _source_budgets(spec: CascadeSpec, budgets: tuple[int, ...],
                    width: int, top_l: int) -> tuple[int, ...]:
    """Clamp the resolved budget ladder to a sourced stage 1's candidate
    ``width`` — the source already pruned below any larger budget."""
    if width < top_l:
        raise ValueError(
            f"candidate source emits {width} rows per query, fewer than "
            f"top_l={top_l} ({spec.describe()})")
    return tuple(min(b, width) for b in budgets)


def stage_keys(spec: CascadeSpec) -> tuple[str, ...]:
    """``stage<i>.<method>`` of each pruning stage, then
    ``rescore.<rescorer>``: the keys of :func:`stage_rows`, and under
    ``emd.cascade.`` the profiler scope of each stage."""
    return tuple(f"stage{i + 1}.{s.method}"
                 for i, s in enumerate(spec.stages)) + (
        f"rescore.{spec.rescorer}",)


def stage_rows(spec: CascadeSpec, n: int, top_l: int) -> dict[str, int]:
    """Rows scored per query by each stage of ``spec`` on an ``n``-row
    corpus: stage 1 reads the full corpus — or, sourced, only the
    source's candidate width — later stages and the rescorer read the
    previous stage's survivors (the budget ladder)."""
    budgets = spec.resolve_budgets(n, top_l)
    prev = n
    if spec.sourced:
        width = spec.source.width
        if width is not None:
            prev = min(width, n)
            budgets = _source_budgets(spec, budgets, prev, top_l)
    return dict(zip(stage_keys(spec), (prev, *budgets), strict=True))


def _row_order(cand: Array, cmask: Array | None):
    """Survivors sorted by global row id (their mask alongside). Every
    later selection takes the lowest POSITION among exactly tied scores;
    in row order that is the lowest row id — the full-corpus top-k's own
    rule, without which a tie at the top-l boundary could return another
    row than full-corpus search does."""
    if cmask is None:
        return jnp.sort(cand, axis=1), None
    return tuple(jax.lax.sort((cand, cmask), dimension=1, num_keys=1))


def _prune(corpus: lc.Corpus, Q_ids: Array, Q_w: Array, spec: CascadeSpec,
           budgets: tuple[int, ...], *, n_valid, topk_blocks, engine,
           source=None, **knobs):
    """Run the pruning ladder; returns ``(cand, cmask)``: the
    (nq, budgets[-1]) global row ids surviving every stage, in row order
    (:func:`_row_order`), plus their validity mask when stage 1 was fed
    by a sublinear source (``None`` on the full-scan path, where every
    survivor is real). Traced under jit by the callers.

    Full scan keeps the original path BITWISE: full-corpus
    ``batch_scores`` + (shard-blocked) top-budget. A sourced stage 1
    instead scores only the source's candidate rows through the
    method's candidate-compacted engine, with the source's invalid
    slots (under-full buckets) pushed to ``lc.PAD_DIST`` so they rank
    last; the mask rides along the ladder because a later gather can
    still select one when a query's probed buckets hold fewer real rows
    than the final budget.
    """
    first = spec.stages[0]
    keys = stage_keys(spec)
    with jax.named_scope(scopes.cascade(keys[0])):
        if source is None or source.spec.full_scan:
            s = retrieval.batch_scores(corpus, Q_ids, Q_w,
                                       method=first.method,
                                       iters=first.iters, engine=engine,
                                       **knobs)
            _, cand = topk_smallest(lc.mask_pad_rows(s, n_valid),
                                    budgets[0], topk_blocks)
            cmask = None
        else:
            cand, cmask = source.candidates(corpus, Q_ids, Q_w)
            sc = retrieval.cand_scores(corpus, Q_ids, Q_w, cand,
                                       method=first.method,
                                       iters=first.iters, **knobs)
            sc = jnp.where(cmask, sc, lc.PAD_DIST)
            _, pos = topk_smallest(sc, budgets[0])
            cand = jnp.take_along_axis(cand, pos, axis=1)
            cmask = jnp.take_along_axis(cmask, pos, axis=1)
        cand, cmask = _row_order(cand, cmask)
    for key, stage, b in zip(keys[1:-1], spec.stages[1:], budgets[1:],
                             strict=True):
        with jax.named_scope(scopes.cascade(key)):
            sc = retrieval.cand_scores(corpus, Q_ids, Q_w, cand,
                                       method=stage.method,
                                       iters=stage.iters, **knobs)
            if cmask is not None:
                sc = jnp.where(cmask, sc, lc.PAD_DIST)
            _, pos = topk_smallest(sc, b)
            cand = jnp.take_along_axis(cand, pos, axis=1)
            if cmask is not None:
                cmask = jnp.take_along_axis(cmask, pos, axis=1)
            cand, cmask = _row_order(cand, cmask)
    return cand, cmask


def _resolved_budgets(spec: CascadeSpec, source, n: int,
                      top_l: int) -> tuple[int, ...]:
    """Budget ladder for one search: fraction resolution + sourced
    clamping to the built source's (static) candidate width."""
    budgets = spec.resolve_budgets(n, top_l)
    if source is not None and not source.spec.full_scan:
        budgets = _source_budgets(spec, budgets, source.width, top_l)
    return budgets


@functools.partial(jax.jit, static_argnames=("spec", "top_l", "n_valid",
                                             "topk_blocks", "engine")
                   + _KNOBS)
def _cascade_device(corpus: lc.Corpus, Q_ids: Array, Q_w: Array,
                    spec: CascadeSpec, top_l: int, *, n_valid=None,
                    topk_blocks: int = 1, engine: str = "batched",
                    source=None, **knobs) -> CascadeResult:
    """Whole ladder + jittable rescorer as ONE jitted program. ``source``
    is a built candidate source (a pytree argument — its spec rides in
    the treedef, so distinct indexes of the same spec share a compile)."""
    n = n_valid if n_valid is not None else corpus.n
    budgets = _resolved_budgets(spec, source, n, top_l)
    cand, cmask = _prune(corpus, Q_ids, Q_w, spec, budgets,
                         n_valid=n_valid, topk_blocks=topk_blocks,
                         engine=engine, source=source, **knobs)
    fn = rescore.resolve(spec.rescorer).fn
    with jax.named_scope(scopes.cascade(stage_keys(spec)[-1])):
        rescored = fn(corpus, Q_ids, Q_w, cand, iters=spec.rescorer_iters,
                      **knobs)
        if cmask is not None:
            rescored = jnp.where(cmask, rescored, lc.PAD_DIST)
        vals, pos = topk_smallest(rescored, top_l)
        return CascadeResult(vals, jnp.take_along_axis(cand, pos, axis=1))


@functools.partial(jax.jit, static_argnames=("spec", "top_l", "n_valid",
                                             "topk_blocks", "engine")
                   + _KNOBS)
def _prune_jit(corpus, Q_ids, Q_w, spec, top_l, *, n_valid=None,
               topk_blocks=1, engine="batched", source=None, **knobs):
    n = n_valid if n_valid is not None else corpus.n
    budgets = _resolved_budgets(spec, source, n, top_l)
    return _prune(corpus, Q_ids, Q_w, spec, budgets, n_valid=n_valid,
                  topk_blocks=topk_blocks, engine=engine, source=source,
                  **knobs)


def cascade_search(corpus: lc.Corpus, Q_ids: Array, Q_w: Array,
                   spec: CascadeSpec | str, top_l: int, *,
                   n_valid: int | None = None, topk_blocks: int = 1,
                   engine: str = "batched", use_kernels: bool = False,
                   block_v: int = 256, block_h: int = 256,
                   block_n: int = 256, rev_block: int = 256,
                   block_q: int = 8, mesh=None, precision: str = "f32",
                   source=None) -> CascadeResult:
    """Cascaded top-l search of a ``(nq, h)`` query batch.

    ``spec`` is a :class:`~repro.cascade.spec.CascadeSpec` or a preset
    name from :data:`~repro.cascade.spec.CASCADES`. ``n_valid`` masks
    zero-weight pad rows beyond it out of candidacy (the distributed
    step's padded corpora); ``topk_blocks`` picks the shard-blocked
    stage-1 top-budget (the mesh step passes its model-axis size). The
    remaining knobs mirror ``retrieval.batch_scores``; ``use_kernels``
    routes the full-corpus stage-1 scoring through the Phase-1/2 kernels
    AND every candidate stage + jittable registry rescorer through the
    candidate kernels (``kernels/cand_pour`` — per-query gather and
    reduction, matching the reference candidate engines to
    within a few ulps, so an admissible cascade's exact-top-l guarantee is
    unchanged; ``block_n``/``block_v`` tile them). ``mesh`` (static,
    hashable) routes the kernel path of every stage through the
    ``kernels/partition`` shard_map shims when its axes divide — this is
    how the distributed step runs the kernel cascade COMPILED.

    ``source`` is a BUILT candidate source (``spec.source.build(corpus)``
    or the one ``EmdIndex.build`` stores) and is required when
    ``spec.sourced``: stage 1 then scores only the sourced candidates,
    breaking the O(n) stage-1 wall — at the price of measured recall.
    """
    spec = resolve_spec(spec)
    if spec.sourced:
        if source is None:
            raise ValueError(
                f"cascade {spec.describe()} is sourced but no built "
                "candidate source was passed; build one with "
                "spec.source.build(corpus) (EmdIndex does this for you)")
        if source.spec != spec.source:
            raise ValueError(
                f"built source {source.spec.describe()} does not match "
                f"the cascade's source spec {spec.source.describe()}")
    elif source is not None and not source.spec.full_scan:
        raise ValueError(
            f"a {source.spec.describe()} source was passed but cascade "
            f"{spec.describe()} does not declare one (set "
            "CascadeSpec.source so admissibility accounting sees it)")
    knobs = dict(engine=engine, use_kernels=use_kernels, block_v=block_v,
                 block_h=block_h, block_n=block_n, rev_block=rev_block,
                 block_q=block_q, mesh=mesh, precision=precision)
    if rescore.resolve(spec.rescorer).jittable:
        return _cascade_device(corpus, Q_ids, Q_w, spec, top_l,
                               n_valid=n_valid, topk_blocks=topk_blocks,
                               source=source, **knobs)
    # Host rescorer (exact emd): device pruning, numpy rescoring.
    cand, cmask = _prune_jit(corpus, Q_ids, Q_w, spec, top_l,
                             n_valid=n_valid, topk_blocks=topk_blocks,
                             source=source, **knobs)
    cand = np.asarray(cand)
    rescored = rescore.resolve(spec.rescorer).host_fn(corpus, Q_ids, Q_w,
                                                      cand)
    if cmask is not None:
        rescored = np.where(np.asarray(cmask), rescored, lc.PAD_DIST)
    pos = np.argsort(rescored, axis=1, kind="stable")[:, :top_l]
    return CascadeResult(
        jnp.asarray(np.take_along_axis(rescored, pos, axis=1),
                    jnp.float32),
        jnp.asarray(np.take_along_axis(cand, pos, axis=1), jnp.int32))


def topk_recall(indices, ref_indices) -> float:
    """Fraction of the reference top-l retrieved by ``indices``, averaged
    over queries — the cascade-vs-full agreement number reported by
    ``benchmarks/bench_cascade.py`` (1.0 for an admissible cascade with
    sufficient budgets). Delegates to :func:`retrieval.topl_overlap`."""
    return retrieval.topl_overlap(indices, ref_indices)
