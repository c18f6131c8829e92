"""Top-l nearest-neighbor retrieval on top of the LC engines.

This is the paper's evaluation harness (Section 6) as a library: every
document is a query, scored against the whole corpus, and precision@top-l
is the fraction of retrieved neighbors sharing the query's label.

The registry is typed: every entry is a :class:`MethodSpec` whose scorer
shares one uniform signature, so ``search`` / ``all_pairs_scores`` jit
end-to-end with no per-method special-casing. ``search`` runs one query;
``batch_scores`` runs a query batch through the method's multi-query
engine (Phase 1 amortized across the batch; ``engine="scan"`` falls back
to the per-query graph); ``all_pairs_scores`` builds the full n x n bound
matrix and symmetrizes it unless the method is already symmetric.

NOTE (serving callers): prefer ``repro.api.EmdIndex`` — the unified facade
over this module, the Pallas kernels, and the distributed engine in
``launch/search.py``. This module remains the thin compute layer the
facade composes.
"""
from __future__ import annotations

import dataclasses
import functools
from collections.abc import Callable
from typing import Protocol

import jax
import jax.numpy as jnp

from repro.core import lc
from repro.core.geometry import weighted_centroids
from repro.core.precision import matmul_precision

Array = jax.Array


class ScoreFn(Protocol):
    """Uniform scorer signature every registered method implements.

    Scores ONE query histogram (``q_ids``/``q_w``, each ``(h,)``) against
    all ``n`` database rows, returning ``(n,)`` distances (lower = more
    similar). Methods ignore the kwargs they do not use.
    """

    def __call__(self, corpus: lc.Corpus, q_ids: Array, q_w: Array, *,
                 iters: int = 1, use_kernels: bool = False,
                 block_v: int = 256, block_h: int = 256, block_n: int = 256,
                 rev_block: int = 256, block_q: int = 8) -> Array: ...


@dataclasses.dataclass(frozen=True)
class MethodSpec:
    """Typed registry entry for one scoring method.

    name:        registry key (``EngineConfig.method`` value).
    paper_name:  the paper's name for the measure (README table).
    fn:          uniform-signature scorer (see :class:`ScoreFn`).
    symmetric:   True if the measure is symmetric in (query, db) — its
                 all-pairs matrix needs no max-symmetrization (BoW, WCD).
    uses_iters:  True if ``iters`` changes the result (LC-ACT only).
    supports_kernels: True if ``use_kernels=True`` routes through the
                 fused Pallas kernels rather than silently falling back.
    reverse:     registry name of the opposite-direction bound, if one
                 exists (rwmd <-> rwmd_rev); enables the per-query
                 symmetric path ``symmetric_query_scores``.
    batch_fn:    multi-query scorer with the same uniform signature but
                 (nq, h) queries -> (nq, n) scores; amortizes Phase 1
                 across the batch. ``None`` falls back to the scanned
                 per-query path in ``batch_scores``.
    dist_fn:     mesh-specialized multi-query scorer for the distributed
                 step (``engine="dist"``). Most methods distribute via
                 their ``batch_fn`` unchanged — the lc pipeline stages
                 carry their own ``sharding.annotate`` constraints — so
                 ``None`` means "use batch_fn". Register one only when
                 the single-host schedule fights the partitioner (e.g.
                 rwmd_rev's row-block scan would gather the
                 model-sharded rows).
    symmetric_batch_fn: multi-query scorer for the SYMMETRIC measure
                 (max of both directions) that shares intermediate work
                 between the two — rwmd/rwmd_rev share one stacked
                 Phase-1 distance tensor. ``None`` falls back to two
                 directional calls.
    dist_out:    PartitionSpec-shaped hint for the (nq, n) score matrix
                 the distributed step emits; ``"data"`` resolves to the
                 mesh's DP axes. Default: queries on their data shards,
                 database columns on the model shards that scored them.
    cand_fn:     candidate-compacted multi-query scorer for the cascade
                 subsystem (``repro.cascade``): same uniform signature
                 plus a ``cand`` (nq, b) array of per-query candidate row
                 ids, returning (nq, b) scores at those rows only (Phase 1
                 unchanged, Phase 2/3 gather-compacted). ``None`` means
                 the method cannot serve as a cascade stage or rescorer.
                 For the five LC methods ``use_kernels=True`` routes the
                 gather + reduction through the fused candidate Pallas
                 kernels (``kernels/cand_pour``; ``block_n`` tiles the
                 candidate rows, ``block_v`` the in-kernel one-hot gather
                 of ``rwmd_rev`` and ``ict``),
                 matching the reference path to within a few ulps
                 (gather exact, same reduction formulas); the bow/wcd baselines
                 have no kernel form and ignore the flag.
    """
    name: str
    paper_name: str
    fn: ScoreFn
    symmetric: bool = False
    uses_iters: bool = False
    supports_kernels: bool = False
    reverse: str | None = None
    batch_fn: ScoreFn | None = None
    dist_fn: ScoreFn | None = None
    symmetric_batch_fn: ScoreFn | None = None
    dist_out: tuple = ("data", "model")
    cand_fn: Callable | None = None


METHODS: dict[str, MethodSpec] = {}


def _register(name: str, *, paper_name: str, symmetric: bool = False,
              uses_iters: bool = False, supports_kernels: bool = False,
              reverse: str | None = None) -> Callable[[ScoreFn], ScoreFn]:
    def deco(fn: ScoreFn) -> ScoreFn:
        METHODS[name] = MethodSpec(name=name, paper_name=paper_name, fn=fn,
                                   symmetric=symmetric, uses_iters=uses_iters,
                                   supports_kernels=supports_kernels,
                                   reverse=reverse)
        return fn
    return deco


def _register_batch(name: str) -> Callable[[ScoreFn], ScoreFn]:
    """Attach a batched (multi-query) scorer to an already-registered
    method; the single-query ``fn`` stays the parity oracle."""
    def deco(fn: ScoreFn) -> ScoreFn:
        METHODS[name] = dataclasses.replace(METHODS[name], batch_fn=fn)
        return fn
    return deco


def _register_dist(name: str) -> Callable[[ScoreFn], ScoreFn]:
    """Attach a mesh-specialized scorer (``engine="dist"`` override)."""
    def deco(fn: ScoreFn) -> ScoreFn:
        METHODS[name] = dataclasses.replace(METHODS[name], dist_fn=fn)
        return fn
    return deco


def _register_cand(name: str) -> Callable[[Callable], Callable]:
    """Attach a candidate-compacted scorer (cascade stages/rescoring)."""
    def deco(fn: Callable) -> Callable:
        METHODS[name] = dataclasses.replace(METHODS[name], cand_fn=fn)
        return fn
    return deco


def _register_symmetric_batch(*names: str) -> Callable[[ScoreFn], ScoreFn]:
    """Attach a shared-work symmetric multi-query scorer to a
    reverse-linked method pair (both directions symmetrize identically)."""
    def deco(fn: ScoreFn) -> ScoreFn:
        for name in names:
            METHODS[name] = dataclasses.replace(METHODS[name],
                                                symmetric_batch_fn=fn)
        return fn
    return deco


@_register("rwmd", paper_name="LC-RWMD (db -> query)",
           supports_kernels=True, reverse="rwmd_rev")
def _rwmd(corpus, q_ids, q_w, *, use_kernels=False, block_v=256,
          block_h=256, **_):
    return lc.lc_rwmd_scores(corpus, q_ids, q_w, use_kernels=use_kernels,
                             block_v=block_v, block_h=block_h)


@_register_batch("rwmd")
def _rwmd_batch(corpus, q_ids, q_w, *, use_kernels=False, block_v=256,
                block_h=256, block_q=8, mesh=None, precision="f32", **_):
    return lc.lc_rwmd_scores_batched(corpus, q_ids, q_w,
                                     use_kernels=use_kernels,
                                     block_q=block_q, block_v=block_v,
                                     block_h=block_h, mesh=mesh,
                                     precision=precision)


@_register("rwmd_rev", paper_name="LC-RWMD (query -> db)", reverse="rwmd")
def _rwmd_rev(corpus, q_ids, q_w, *, rev_block=256, **_):
    return lc.lc_rwmd_scores_rev(corpus, q_ids, q_w, block=rev_block)


@_register_batch("rwmd_rev")
def _rwmd_rev_batch(corpus, q_ids, q_w, *, rev_block=256, block_q=8,
                    precision="f32", **_):
    return lc.lc_rwmd_scores_rev_batched(corpus, q_ids, q_w, block=rev_block,
                                         block_q=block_q,
                                         precision=precision)


@_register_dist("rwmd_rev")
def _rwmd_rev_dist(corpus, q_ids, q_w, *, rev_block=256, block_q=8,
                   precision="f32", **_):
    return lc.lc_rwmd_scores_rev_dist(corpus, q_ids, q_w, block=rev_block,
                                      block_q=block_q, precision=precision)


@_register_cand("rwmd")
def _rwmd_cand(corpus, q_ids, q_w, cand, *, block_q=8, use_kernels=False,
               block_n=256, mesh=None, precision="f32", **_):
    return lc.lc_rwmd_scores_cand(corpus, q_ids, q_w, cand, block_q=block_q,
                                  use_kernels=use_kernels, block_n=block_n,
                                  mesh=mesh, precision=precision)


@_register_cand("rwmd_rev")
def _rwmd_rev_cand(corpus, q_ids, q_w, cand, *, block_q=8, use_kernels=False,
                   block_n=256, block_v=256, mesh=None, precision="f32",
                   **_):
    return lc.lc_rwmd_scores_rev_cand(corpus, q_ids, q_w, cand,
                                      block_q=block_q,
                                      use_kernels=use_kernels,
                                      block_n=block_n, block_v=block_v,
                                      mesh=mesh, precision=precision)


@_register_symmetric_batch("rwmd", "rwmd_rev")
def _rwmd_symmetric_batch(corpus, q_ids, q_w, *, rev_block=256, block_q=8,
                          dist=False, precision="f32", **_):
    # ``dist`` is passed by batch_scores(engine="dist") only: it selects
    # the mesh-friendly full-row reverse reduction.
    return lc.lc_rwmd_symmetric_scores_batched(corpus, q_ids, q_w,
                                               block=rev_block,
                                               block_q=block_q,
                                               full_rows=dist,
                                               precision=precision)


@_register("omr", paper_name="LC-OMR", supports_kernels=True)
def _omr(corpus, q_ids, q_w, *, use_kernels=False, block_v=256,
         block_h=256, **_):
    return lc.lc_omr_scores(corpus, q_ids, q_w, use_kernels=use_kernels,
                            block_v=block_v, block_h=block_h)


@_register_batch("omr")
def _omr_batch(corpus, q_ids, q_w, *, use_kernels=False, block_v=256,
               block_h=256, block_q=8, mesh=None, precision="f32", **_):
    return lc.lc_omr_scores_batched(corpus, q_ids, q_w,
                                    use_kernels=use_kernels, block_q=block_q,
                                    block_v=block_v, block_h=block_h,
                                    mesh=mesh, precision=precision)


@_register_cand("omr")
def _omr_cand(corpus, q_ids, q_w, cand, *, block_q=8, use_kernels=False,
              block_n=256, mesh=None, precision="f32", **_):
    return lc.lc_omr_scores_cand(corpus, q_ids, q_w, cand, block_q=block_q,
                                 use_kernels=use_kernels, block_n=block_n,
                                 mesh=mesh, precision=precision)


@_register("act", paper_name="LC-ACT-k", uses_iters=True,
           supports_kernels=True)
def _act(corpus, q_ids, q_w, *, iters=1, use_kernels=False, block_v=256,
         block_h=256, block_n=256, **_):
    return lc.lc_act_scores(corpus, q_ids, q_w, iters=iters,
                            use_kernels=use_kernels, block_v=block_v,
                            block_h=block_h, block_n=block_n)


@_register_batch("act")
def _act_batch(corpus, q_ids, q_w, *, iters=1, use_kernels=False,
               block_v=256, block_h=256, block_n=256, block_q=8, mesh=None,
               precision="f32", segments=None, **_):
    return lc.lc_act_scores_batched(corpus, q_ids, q_w, iters=iters,
                                    use_kernels=use_kernels, block_q=block_q,
                                    block_v=block_v, block_h=block_h,
                                    block_n=block_n, mesh=mesh,
                                    precision=precision, segments=segments)


@_register_cand("act")
def _act_cand(corpus, q_ids, q_w, cand, *, iters=1, block_q=8,
              use_kernels=False, block_n=256, mesh=None, precision="f32",
              **_):
    return lc.lc_act_scores_cand(corpus, q_ids, q_w, cand, iters=iters,
                                 block_q=block_q, use_kernels=use_kernels,
                                 block_n=block_n, mesh=mesh,
                                 precision=precision)


@_register("ict", paper_name="LC-ICT (db -> query)")
def _ict(corpus, q_ids, q_w, **_):
    """The paper's tightest linear-complexity bound (Algorithm 2, full
    cost-sorted ladder): Theorem 2 places it between ACT-k and exact EMD.
    Too heavy for full-corpus serving (per-entry sort over h); its role
    is the cascade rescorer on pruned candidate sets."""
    return lc.lc_ict_scores(corpus, q_ids, q_w)


@_register_batch("ict")
def _ict_batch(corpus, q_ids, q_w, *, block_q=8, precision="f32", **_):
    return lc.lc_ict_scores_batched(corpus, q_ids, q_w, block_q=block_q,
                                    precision=precision)


@_register_cand("ict")
def _ict_cand(corpus, q_ids, q_w, cand, *, block_q=8, use_kernels=False,
              block_n=256, block_v=256, mesh=None, precision="f32", **_):
    return lc.lc_ict_scores_cand(corpus, q_ids, q_w, cand, block_q=block_q,
                                 use_kernels=use_kernels, block_n=block_n,
                                 block_v=block_v, mesh=mesh,
                                 precision=precision)


@_register("bow", paper_name="BoW cosine baseline", symmetric=True)
def _bow(corpus, q_ids, q_w, **_):
    """Bag-of-words cosine baseline (O(nh)): 1 - cosine as a distance."""
    qv = jnp.zeros((corpus.v,), corpus.w.dtype).at[q_ids].add(q_w)
    qv = qv / jnp.maximum(jnp.linalg.norm(qv), 1e-12)
    wn = corpus.w / jnp.maximum(
        jnp.linalg.norm(corpus.w, axis=1, keepdims=True), 1e-12)
    dots = jnp.sum(wn * qv[corpus.ids], axis=1)
    return 1.0 - dots


@_register_batch("bow")
def _bow_batch(corpus, q_ids, q_w, **_):
    nq = q_ids.shape[0]
    qv = jnp.zeros((nq, corpus.v), corpus.w.dtype)
    qv = qv.at[jnp.arange(nq)[:, None], q_ids].add(q_w)
    qv = qv / jnp.maximum(jnp.linalg.norm(qv, axis=1, keepdims=True), 1e-12)
    wn = corpus.w / jnp.maximum(
        jnp.linalg.norm(corpus.w, axis=1, keepdims=True), 1e-12)
    dots = jnp.einsum("us,qus->qu", wn, qv[:, corpus.ids],
                      precision=matmul_precision(wn.dtype))
    return 1.0 - dots


@_register_cand("bow")
def _bow_cand(corpus, q_ids, q_w, cand, **_):
    nq = q_ids.shape[0]
    qv = jnp.zeros((nq, corpus.v), corpus.w.dtype)
    qv = qv.at[jnp.arange(nq)[:, None], q_ids].add(q_w)
    qv = qv / jnp.maximum(jnp.linalg.norm(qv, axis=1, keepdims=True), 1e-12)
    w_c = corpus.w[cand]                                  # (nq, b, hmax)
    wn = w_c / jnp.maximum(
        jnp.linalg.norm(w_c, axis=-1, keepdims=True), 1e-12)
    qg = lc.gather_per_query(qv, corpus.ids[cand])
    return 1.0 - jnp.einsum("qbs,qbs->qb", wn, qg,
                            precision=matmul_precision(wn.dtype))


#: Histogram slots per step of the centroid reduction: gathering every
#: entry's embedding at once is an (n, hmax, m) tensor — 11 GB at
#: 20News widths, more than a chip holds.
CENTROID_SLOTS = 64


def _corpus_centroids(corpus) -> Array:
    """(n, m) weight-centroid of every corpus row, accumulated over
    blocks of ``CENTROID_SLOTS`` histogram slots (the row axis stays
    whole, so a row-sharded corpus stays sharded)."""
    n, hmax = corpus.ids.shape
    steps = -(-hmax // CENTROID_SLOTS)
    if steps == 1:
        return weighted_centroids(corpus.w, corpus.coords[corpus.ids])
    pad = ((0, 0), (0, steps * CENTROID_SLOTS - hmax))   # id 0, weight 0
    ids, w = jnp.pad(corpus.ids, pad), jnp.pad(corpus.w, pad)

    def step(j, acc):
        i = jax.lax.dynamic_slice_in_dim(ids, j * CENTROID_SLOTS,
                                         CENTROID_SLOTS, 1)
        x = jax.lax.dynamic_slice_in_dim(w, j * CENTROID_SLOTS,
                                         CENTROID_SLOTS, 1)
        return acc + weighted_centroids(x, corpus.coords[i])
    return jax.lax.fori_loop(0, steps, step,
                             jnp.zeros((n, corpus.m), corpus.coords.dtype))


@_register("wcd", paper_name="Word Centroid Distance baseline",
           symmetric=True)
def _wcd(corpus, q_ids, q_w, **_):
    """Word Centroid Distance baseline (O(nm))."""
    qc = weighted_centroids(q_w, corpus.coords[q_ids])    # (m,)
    return jnp.linalg.norm(_corpus_centroids(corpus) - qc[None, :], axis=1)


@_register_batch("wcd")
def _wcd_batch(corpus, q_ids, q_w, **_):
    qc = weighted_centroids(q_w, corpus.coords[q_ids])
    cent = _corpus_centroids(corpus)
    return jnp.linalg.norm(cent[None, :] - qc[:, None], axis=-1)


@_register_cand("wcd")
def _wcd_cand(corpus, q_ids, q_w, cand, **_):
    # Centroids only for the (nq, b) candidate rows — materializing all
    # n through the gather would waste O(n/b) of the work.
    qc = weighted_centroids(q_w, corpus.coords[q_ids])
    cent = weighted_centroids(corpus.w[cand],
                              corpus.coords[corpus.ids[cand]])
    return jnp.linalg.norm(cent - qc[:, None, :], axis=-1)


_STATIC_KW = ("method", "iters", "use_kernels", "block_v", "block_h",
              "block_n", "rev_block", "block_q", "precision")


@functools.partial(jax.jit,
                   static_argnames=("method", "symmetric") + _STATIC_KW[1:])
def query_scores(corpus: lc.Corpus, q_ids: Array, q_w: Array, *,
                 method: str = "act", symmetric: bool = False,
                 iters: int = 1, use_kernels: bool = False,
                 block_v: int = 256, block_h: int = 256, block_n: int = 256,
                 rev_block: int = 256, block_q: int = 8,
                 precision: str = "f32") -> Array:
    """One query against the whole database, jitted end-to-end.

    ``symmetric=True`` returns the paper's symmetric measure for a single
    query: the max of the two directional bounds (requires a method with a
    registered ``reverse``, i.e. rwmd / rwmd_rev).

    ``precision`` is accepted for kwarg parity with :func:`batch_scores`,
    but the single-query engines are the full-precision parity oracle —
    they always run float32, so it has no effect here.
    """
    spec = METHODS[method]
    kw = dict(iters=iters, use_kernels=use_kernels, block_v=block_v,
              block_h=block_h, block_n=block_n, rev_block=rev_block)
    fwd = spec.fn(corpus, q_ids, q_w, **kw)
    if not symmetric or spec.symmetric:
        return fwd
    if spec.reverse is None:
        raise ValueError(
            f"method {method!r} has no reverse direction registered; "
            "per-query symmetric scoring needs one (use rwmd/rwmd_rev)")
    return jnp.maximum(fwd, METHODS[spec.reverse].fn(corpus, q_ids, q_w, **kw))


@functools.partial(jax.jit,
                   static_argnames=("method", "symmetric", "engine", "mesh")
                   + _STATIC_KW[1:])
def batch_scores(corpus: lc.Corpus, q_ids: Array, q_w: Array, *,
                 method: str = "act", symmetric: bool = False,
                 engine: str = "batched", iters: int = 1,
                 use_kernels: bool = False, block_v: int = 256,
                 block_h: int = 256, block_n: int = 256,
                 rev_block: int = 256, block_q: int = 8, mesh=None,
                 precision: str = "f32",
                 segments: lc.Segments | None = None) -> Array:
    """Batch of queries ``(nq, h)`` -> ``(nq, n)`` score matrix.

    ``engine="batched"`` (default) dispatches to the method's multi-query
    engine: Phase 1 (the vocabulary-vs-query distance work) runs ONCE for
    the whole batch and Phase 2/3 stream query blocks of ``block_q`` —
    this is the serving hot path. ``engine="dist"`` is the same pipeline
    with mesh-specialized overrides where registered (``spec.dist_fn``);
    it is what the distributed step in ``launch/search.py`` traces — the
    pipeline stages carry their own sharding constraints, so on a single
    host it scores identically to ``batched``. ``mesh`` (static, hashable)
    additionally routes the kernel path through the ``kernels/partition``
    shard_map shims when its axes divide the problem — required for
    COMPILED ``pallas_call`` on a mesh, which has no SPMD partitioning
    rule of its own. ``engine="scan"`` is the
    fallback that runs each query through the exact single-query compute
    graph via ``lax.map``, matching a Python loop of ``query_scores``
    calls bit-for-bit; use it to verify the batched engine or on methods
    without a registered ``batch_fn``. ``segments``: the corpus's
    segmented row layout (``lc.segment_rows``), read by the batched ACT
    kernel pour only.
    """
    if engine not in ("batched", "scan", "dist"):
        raise ValueError(f"unknown engine {engine!r}; "
                         "one of ('batched', 'scan', 'dist')")
    spec = METHODS[method]
    if engine != "scan" and spec.batch_fn is not None:
        def pick(s):
            return (s.dist_fn or s.batch_fn) if engine == "dist" \
                else s.batch_fn
        kw = dict(iters=iters, use_kernels=use_kernels, block_v=block_v,
                  block_h=block_h, block_n=block_n, rev_block=rev_block,
                  block_q=block_q, mesh=mesh, precision=precision,
                  segments=segments)
        if symmetric and not spec.symmetric:
            if spec.reverse is None:
                raise ValueError(
                    f"method {method!r} has no reverse direction "
                    "registered; symmetric scoring needs one (use "
                    "rwmd/rwmd_rev)")
            if spec.symmetric_batch_fn is not None and not use_kernels:
                # Shared-work symmetric engine: both directions read one
                # stacked Phase-1 distance tensor (kernel Phase 1 has no
                # shared form — fall through to two directional calls).
                return spec.symmetric_batch_fn(corpus, q_ids, q_w,
                                               dist=(engine == "dist"), **kw)
            fwd = pick(spec)(corpus, q_ids, q_w, **kw)
            rspec = METHODS[spec.reverse]
            if rspec.batch_fn is not None:
                return jnp.maximum(fwd, pick(rspec)(corpus, q_ids, q_w,
                                                    **kw))
            rev = jax.lax.map(lambda ab: rspec.fn(corpus, ab[0], ab[1],
                                                  **kw), (q_ids, q_w))
            return jnp.maximum(fwd, rev)
        return pick(spec)(corpus, q_ids, q_w, **kw)

    def one(ab):
        return query_scores(corpus, ab[0], ab[1], method=method,
                            symmetric=symmetric, iters=iters,
                            use_kernels=use_kernels, block_v=block_v,
                            block_h=block_h, block_n=block_n,
                            rev_block=rev_block)
    return jax.lax.map(one, (q_ids, q_w))


@functools.partial(jax.jit,
                   static_argnames=("top_l", "symmetric") + _STATIC_KW)
def search(corpus: lc.Corpus, q_ids: Array, q_w: Array, top_l: int,
           method: str = "act", iters: int = 1, *, symmetric: bool = False,
           use_kernels: bool = False, block_v: int = 256, block_h: int = 256,
           block_n: int = 256, rev_block: int = 256, block_q: int = 8,
           precision: str = "f32"):
    """Return (scores, indices) of the top-l most similar database rows.

    Jitted end-to-end (method dispatch is static), so scoring + top-k
    compile into one program instead of re-tracing the method per call.
    """
    scores = query_scores(corpus, q_ids, q_w, method=method,
                          symmetric=symmetric, iters=iters,
                          use_kernels=use_kernels, block_v=block_v,
                          block_h=block_h, block_n=block_n,
                          rev_block=rev_block)
    neg, idx = jax.lax.top_k(-scores, top_l)
    return -neg, idx


@functools.partial(jax.jit, static_argnames=_STATIC_KW + ("engine",))
def all_pairs_scores(corpus: lc.Corpus, method: str = "act",
                     iters: int = 1, *, engine: str = "batched",
                     use_kernels: bool = False,
                     block_v: int = 256, block_h: int = 256,
                     block_n: int = 256, rev_block: int = 256,
                     block_q: int = 8, precision: str = "f32") -> Array:
    """n x n symmetric bound matrix over the corpus (paper's eval mode).

    asym[a, b] = directional bound of moving histogram b INTO histogram a
    (query = row a); symmetric = max(asym, asym^T) unless the method's
    spec declares the measure already symmetric. ``engine`` selects the
    batched multi-query engine or the scanned per-query fallback (see
    ``batch_scores``).
    """
    spec = METHODS[method]
    asym = batch_scores(corpus, corpus.ids, corpus.w, method=method,
                        engine=engine, iters=iters, use_kernels=use_kernels,
                        block_v=block_v, block_h=block_h, block_n=block_n,
                        rev_block=rev_block, block_q=block_q,
                        precision=precision)
    if spec.symmetric:
        return asym
    return lc.symmetric_scores(asym)


@functools.partial(jax.jit,
                   static_argnames=("method", "mesh") + _STATIC_KW[1:])
def cand_scores(corpus: lc.Corpus, q_ids: Array, q_w: Array, cand: Array, *,
                method: str = "act", iters: int = 1,
                use_kernels: bool = False, block_v: int = 256,
                block_h: int = 256, block_n: int = 256,
                rev_block: int = 256, block_q: int = 8, mesh=None,
                precision: str = "f32") -> Array:
    """Candidate-compacted scoring: ``(nq, h)`` queries against each
    query's own ``(b,)`` candidate rows -> ``(nq, b)`` scores.

    This is the cascade subsystem's stage primitive (Phase 1 is shared
    with the full-corpus engines; only Phase 2/3 compacts to the
    candidates), dispatched through ``MethodSpec.cand_fn``.
    ``use_kernels=True`` runs the per-query candidate gather and the
    reduction through the ``kernels/cand_pour`` kernels for the LC methods,
    matching the reference path to within a few ulps (see the
    ``cand_fn`` field doc and ``kernels/cand_pour``'s conformance notes).
    """
    spec = METHODS[method]
    if spec.cand_fn is None:
        raise ValueError(f"method {method!r} has no candidate-compacted "
                         "scorer registered (MethodSpec.cand_fn)")
    return spec.cand_fn(corpus, q_ids, q_w, cand, iters=iters,
                        use_kernels=use_kernels, block_v=block_v,
                        block_h=block_h, block_n=block_n,
                        rev_block=rev_block, block_q=block_q, mesh=mesh,
                        precision=precision)


def _mask_self(scores: Array) -> Array:
    """Push the diagonal of a square corpus-as-queries score matrix to the
    dtype max so a row never retrieves itself.

    The mask is written in the float32 ACCUMULATOR dtype, never a reduced
    storage dtype: ``finfo(bfloat16).max`` is also what bf16 overflow
    saturates to, so masking in-dtype would tie the diagonal with any
    saturated entry and let ``top_k``'s index order pick between self and
    a real row. Upcasting first (exact for bf16/f16) keeps the sentinel
    strictly above every finite score; float32 inputs pass through
    bit-unchanged."""
    n = scores.shape[0]
    acc = jnp.promote_types(scores.dtype, jnp.float32)
    scores = scores.astype(acc)
    big = jnp.asarray(jnp.finfo(acc).max, acc)
    return jnp.where(jnp.eye(n, dtype=bool), big, scores)


def precision_at_l(scores: Array, labels: Array, top_l: int) -> float:
    """Average precision@top-l: fraction of each row's top-l neighbors
    (self excluded) sharing the row's label."""
    _, idx = jax.lax.top_k(-_mask_self(scores), top_l)     # (n, top_l)
    same = labels[idx] == labels[:, None]
    return float(jnp.mean(jnp.mean(same.astype(jnp.float32), axis=1)))


def topl_overlap(got_idx, ref_idx) -> float:
    """Mean fraction of each row's reference index set retrieved by the
    row's ``got_idx`` set — the single home of the top-l agreement
    metric (``recall_at_l`` and ``cascade.topk_recall`` both delegate
    here)."""
    got = jnp.asarray(got_idx)
    ref = jnp.asarray(ref_idx)
    if got.shape != ref.shape:
        raise ValueError(f"index sets must share a shape, got "
                         f"{got.shape} vs {ref.shape}")
    hit = (got[..., :, None] == ref[..., None, :]).any(axis=-1)
    return float(jnp.mean(hit.astype(jnp.float32)))


def recall_at_l(scores: Array, ref_scores: Array, top_l: int, *,
                exclude_self: bool = False) -> float:
    """Average recall@top-l of ``scores`` against a reference ranking:
    the fraction of each row's reference top-l (by ``ref_scores``, e.g.
    exact EMD or full-corpus ACT) that the row's top-l under ``scores``
    retrieves. Shapes must match — (nq, n) query batches or (n, n)
    corpus-as-queries matrices (``exclude_self=True`` masks the diagonal
    of both, the all-pairs convention of :func:`precision_at_l`)."""
    if scores.shape != ref_scores.shape:
        raise ValueError(f"score matrices must share a shape, got "
                         f"{scores.shape} vs {ref_scores.shape}")
    if exclude_self:
        scores = _mask_self(scores)
        ref_scores = _mask_self(ref_scores)
    _, got = jax.lax.top_k(-scores, top_l)
    _, ref = jax.lax.top_k(-ref_scores, top_l)
    return topl_overlap(got, ref)
