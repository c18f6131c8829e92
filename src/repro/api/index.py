"""``EmdIndex``: one serving entry point over every EMD engine.

Build once, query many times — the nearest-neighbor index shape
(build/query phases) the paper's batch algorithms imply. ``build``
precomputes and owns everything reusable across queries: the device-placed
corpus, the method spec, and (for ``backend="distributed"``) the mesh,
shardings, row padding, and jitted multi-query step. Callers then write
identical code whether the engine underneath is the pjit-able jnp
reference, the fused Pallas kernels, or a mesh-sharded multi-host step.

    index = EmdIndex.build(corpus, EngineConfig(method="act", iters=3))
    scores = index.scores(q_ids, q_w)          # (h,) -> (n,)
    scores = index.scores(Q_ids, Q_w)          # (nq, h) -> (nq, n)
    top, idx = index.search(q_ids, q_w)        # top-l neighbors
    S = index.all_pairs()                      # n x n symmetric matrix
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import EngineConfig
from repro.core import lc, retrieval, scopes
from repro.core.lc import Corpus

Array = jax.Array


def _pad_rows(x: Array, n_padded: int) -> Array:
    return jnp.pad(x, ((0, n_padded - x.shape[0]), (0, 0)))


def _ambient_mesh(mesh):
    """``jax.set_mesh(mesh)``, unless ``mesh`` already is the ambient
    mesh — as when a caller jit-compiles ``search`` under it, where
    ``set_mesh`` cannot be entered."""
    if jax.sharding.get_abstract_mesh() == mesh.abstract_mesh:
        return contextlib.nullcontext()
    return jax.set_mesh(mesh)


@dataclasses.dataclass(frozen=True, repr=False)
class EmdIndex:
    """Immutable handle over a built index. Construct via :meth:`build`."""
    corpus: Corpus
    config: EngineConfig
    _mesh: Any = None
    _scores_step: Any = None
    _padded_corpus: Corpus | None = None
    _cascade_step: Any = None
    _tuned: Any = None
    _source: Any = None
    _segments: lc.Segments | None = None
    _gather_fill_pct: float | None = None

    def __repr__(self) -> str:
        mesh = "" if self._mesh is None else f", mesh={dict(self._mesh.shape)}"
        return (f"EmdIndex(n={self.corpus.n}, hmax={self.corpus.hmax}, "
                f"v={self.corpus.v}, m={self.corpus.m}, "
                f"method={self.config.method!r}, "
                f"backend={self.config.backend!r}{mesh})")

    # ------------------------------------------------------------- build
    @classmethod
    def build(cls, corpus: Corpus, config: EngineConfig | None = None, *,
              mesh=None, source=None) -> "EmdIndex":
        """Precompute everything reusable across queries of ``corpus``.

        ``mesh``: distributed backend only — the device mesh to shard
        over; defaults to a single-device (1, 1) data x model mesh so
        single-host callers and multi-host launchers run the same code.

        When the config's cascade names a sublinear candidate source
        (``repro.candidates``), its index is built here too — the
        host-side quantization/tree fit runs once per build, and
        ``search`` consumes the built arrays afterwards. ``source``
        injects an already-built source instead (checkpoint restore;
        must match ``config.source_spec``).

        With ``config.autotune != "off"`` the kernel tile knobs are
        resolved here, once, through ``repro.kernels.autotune`` (cached
        winners under ``"cached"``, a timed sweep of VMEM-admissible
        configs under ``"force"``); the applied picks are recorded on
        :attr:`tuned_blocks` and the jitted steps below compile with
        them baked in.

        Where the scores come from the single-device kernel pour (batched
        LC-ACT with at least one round on ``backend="pallas"``, no
        cascade), the segmented row layout that pour gathers from
        (``lc.segment_rows``) is derived here too, once per build; its
        fill is :attr:`gather_fill_pct`.
        """
        config = EngineConfig() if config is None else config
        tuned: dict = {}
        if config.autotune != "off":
            from repro.kernels import autotune
            config, tuned = autotune.resolve_config(corpus, config)
        src_spec = config.source_spec
        if src_spec is not None and not src_spec.full_scan:
            if source is None:
                source = src_spec.build(corpus)
            elif source.spec != src_spec:
                raise ValueError(
                    f"injected source {source.spec.describe()} does not "
                    f"match config's {src_spec.describe()}")
        else:
            source = None
        if config.backend != "distributed":
            if source is not None:
                source = jax.device_put(source)
            segments = fill = None
            if (config.backend == "pallas" and config.method == "act"
                    and config.effective_iters > 0
                    and config.batch_engine == "batched"
                    and config.cascade is None):
                segments, fill = lc.segment_rows(corpus, config.block_n)
            return cls(corpus=jax.device_put(corpus), config=config,
                       _tuned=tuned, _source=source, _segments=segments,
                       _gather_fill_pct=fill)

        from repro.configs.emd_20news import EMDWorkload
        from repro.launch import mesh as mesh_mod
        from repro.launch import search as dsearch

        mesh = mesh_mod.make_test_mesh(1, 1) if mesh is None else mesh
        n_pad = -(-corpus.n // config.pad_multiple) * config.pad_multiple
        padded = Corpus(ids=_pad_rows(corpus.ids, n_pad),
                        w=_pad_rows(corpus.w, n_pad), coords=corpus.coords)
        workload = EMDWorkload(name="emd-index", n_db=corpus.n,
                               vocab=corpus.v, dim=corpus.m,
                               hmax=corpus.hmax,
                               iters=config.effective_iters, queries=0,
                               method=config.method)
        step = dsearch.jit_scores_step(workload, mesh,
                                       **config.dist_step_kwargs())
        cascade_step = None
        if config.cascade is not None:
            cascade_step = dsearch.jit_cascade_search_step(
                workload, mesh, config.cascade_spec, top_l=config.top_l,
                **config.cascade_step_kwargs())
        in_sh, _ = dsearch.scores_shardings(mesh, workload,
                                            method=config.method)
        padded = Corpus(ids=jax.device_put(padded.ids, in_sh[0]),
                        w=jax.device_put(padded.w, in_sh[1]),
                        coords=jax.device_put(padded.coords, in_sh[2]))
        if source is not None:
            # Small index state, probed at arbitrary buckets: replicated
            # (matches the step's trailing in_shardings).
            from jax.sharding import NamedSharding, PartitionSpec
            source = jax.device_put(source,
                                    NamedSharding(mesh, PartitionSpec()))
        return cls(corpus=corpus, config=config, _mesh=mesh,
                   _scores_step=step, _padded_corpus=padded,
                   _cascade_step=cascade_step, _tuned=tuned,
                   _source=source)

    # --------------------------------------------------------- properties
    @property
    def n(self) -> int:
        """Number of database histograms."""
        return self.corpus.n

    @property
    def backend(self) -> str:
        return self.config.backend

    @property
    def mesh(self):
        """The device mesh (distributed backend), else ``None``."""
        return self._mesh

    @property
    def source(self):
        """The built candidate source feeding cascade stage 1 (``None``
        when the config's cascade is unsourced or full-scan)."""
        return self._source

    @property
    def tuned_blocks(self) -> dict:
        """Autotuned tile picks applied at build: {kernel family ->
        {block knob: tile}}. Empty when ``config.autotune="off"`` or
        nothing was eligible (benches record this next to their
        timings)."""
        return dict(self._tuned or {})

    @property
    def gather_fill_pct(self) -> float | None:
        """Real bins over the slots the kernel pour's ladder gather reads,
        in %, where the index built the segmented layout; else ``None``."""
        return self._gather_fill_pct

    # ------------------------------------------------------------ scoring
    @staticmethod
    def _check_queries(q_ids: Array, q_w: Array) -> tuple[Array, Array,
                                                          bool]:
        """Validate and normalize query input to a ``(nq, h)`` batch;
        returns (ids, w, was_single)."""
        q_ids = jnp.asarray(q_ids)
        q_w = jnp.asarray(q_w)
        if q_ids.ndim not in (1, 2) or q_ids.shape != q_w.shape:
            raise ValueError(
                f"expected matching (h,) or (nq, h) queries, got "
                f"ids {q_ids.shape} / w {q_w.shape}")
        single = q_ids.ndim == 1
        return ((q_ids[None], q_w[None], True) if single
                else (q_ids, q_w, False))

    def _run_dist_step(self, step, qi: Array, qw: Array, *extra):
        """Run a jitted mesh step on a query batch padded to the data-axis
        size (so any nq shards); returns the outputs with pad-query rows
        still attached — callers slice ``[:nq]``. ``extra`` operands
        (e.g. candidate-source state leaves) append after the queries."""
        from repro.launch.mesh import data_axes
        nq = qi.shape[0]
        dp = int(np.prod([self._mesh.shape[a]
                          for a in data_axes(self._mesh)]))
        qi = _pad_rows(qi, -(-nq // dp) * dp)
        qw = _pad_rows(qw, -(-nq // dp) * dp)
        p = self._padded_corpus
        with _ambient_mesh(self._mesh):
            return step(p.ids, p.w, p.coords, qi, qw, *extra)

    def scores(self, q_ids: Array, q_w: Array) -> Array:
        """Directional bound of every database row vs the query/queries.

        Accepts a single query ``(h,)`` -> ``(n,)`` or a batch
        ``(nq, h)`` -> ``(nq, n)``, uniformly across backends. Lower =
        more similar.
        """
        return self._scores(*self._check_queries(q_ids, q_w))

    def _scores(self, qi: Array, qw: Array, single: bool) -> Array:
        """:meth:`scores` of checked queries (:meth:`_check_queries`)."""
        if self.config.backend == "distributed":
            s = self._run_dist_step(self._scores_step, qi, qw)
            s = s[:qi.shape[0], :self.n]   # drop pad queries and pad rows
            return s[0] if single else s
        q_ids, q_w = (qi[0], qw[0]) if single else (qi, qw)
        kw = self.config.score_kwargs()
        if single:
            return retrieval.query_scores(self.corpus, q_ids, q_w,
                                          symmetric=self.config.symmetric,
                                          **kw)
        return retrieval.batch_scores(self.corpus, q_ids, q_w,
                                      symmetric=self.config.symmetric,
                                      engine=self.config.batch_engine,
                                      segments=self._segments, **kw)

    def search(self, q_ids: Array, q_w: Array, top_l: int | None = None, *,
               cascade=None) -> tuple[Array, Array]:
        """(scores, indices) of the top-l most similar database rows,
        ascending; ``(top_l,)`` each for a single query, ``(nq, top_l)``
        for a batch. ``top_l`` defaults to ``config.top_l``.

        ``cascade`` (a ``repro.cascade`` CascadeSpec or preset name,
        defaulting to ``config.cascade``) routes the search through the
        prune-and-rescore ladder instead of full-corpus scoring: scores
        come from the cascade's rescorer, candidates only from rows that
        survived every pruning stage. On ``backend="distributed"`` the
        mesh cascade step is baked at build time from the config, so the
        spec and ``top_l`` cannot be changed per call there.

        Profiler spans (``core.scopes``): ``emd.search`` around the call,
        ``emd.search.check`` (input checks), ``emd.search.score`` (the
        dispatch of the scoring or cascade program) and
        ``emd.search.topl``; the top-l's device work is ``emd.topl``.
        """
        with jax.profiler.TraceAnnotation(scopes.SEARCH):
            top_l = self.config.top_l if top_l is None else top_l
            cascade = self.config.cascade if cascade is None else cascade
            if cascade is not None:
                return self._cascade(q_ids, q_w, top_l, cascade)
            with jax.profiler.TraceAnnotation(scopes.SEARCH + ".check"):
                checked = self._check_queries(q_ids, q_w)
            with jax.profiler.TraceAnnotation(scopes.SEARCH + ".score"):
                s = self._scores(*checked)
            with (jax.profiler.TraceAnnotation(scopes.SEARCH + ".topl"),
                  jax.named_scope(scopes.TOPL)):
                neg, idx = jax.lax.top_k(-s, top_l)
                return -neg, idx

    def _cascade(self, q_ids: Array, q_w: Array, top_l: int,
                 cascade) -> tuple[Array, Array]:
        from repro import cascade as cascade_mod

        if self.config.symmetric:
            raise ValueError(
                "cascade search scores directionally; this index is "
                "configured symmetric=True (same rule EngineConfig "
                "enforces for cascade-in-config)")
        distributed = self.config.backend == "distributed"
        with jax.profiler.TraceAnnotation(scopes.SEARCH + ".check"):
            spec = cascade_mod.resolve_spec(cascade)
            qi, qw, single = self._check_queries(q_ids, q_w)
            if distributed and spec != self.config.cascade_spec:
                raise ValueError(
                    "the distributed cascade step is baked at build time; "
                    "rebuild with EngineConfig(cascade=...) to change the "
                    "spec")
            if distributed and top_l != self.config.top_l:
                raise ValueError(
                    "the distributed cascade step is jitted for "
                    f"top_l={self.config.top_l}; rebuild with "
                    "EngineConfig(top_l=...) to change it")
        with jax.profiler.TraceAnnotation(scopes.SEARCH + ".score"):
            if distributed:
                nq = qi.shape[0]
                leaves = (jax.tree_util.tree_leaves(self._source)
                          if self._source is not None else ())
                scores, idx = self._run_dist_step(self._cascade_step, qi,
                                                  qw, *leaves)
                scores, idx = scores[:nq], idx[:nq]
            else:
                res = cascade_mod.cascade_search(
                    self.corpus, qi, qw, spec, top_l,
                    engine=self.config.batch_engine,
                    source=self._source if spec.sourced else None,
                    **self.config.cascade_knobs())
                scores, idx = res.scores, res.indices
        return (scores[0], idx[0]) if single else (scores, idx)

    def all_pairs(self) -> Array:
        """n x n symmetric score matrix over the corpus (the paper's
        evaluation mode; feed to ``retrieval.precision_at_l``)."""
        if self.config.backend == "distributed":
            # NOTE: with config.symmetric the baked-in step already maxes
            # both directions per pair, so the transpose-max below merely
            # re-symmetrizes float noise — directional scoring would halve
            # the Phase-2 work but needs a second jitted step; all_pairs
            # is the (cold) evaluation path, so compile cost wins.
            asym = self.scores(self.corpus.ids, self.corpus.w)
            if self.config.spec.symmetric:
                return asym
            return lc.symmetric_scores(asym)
        return retrieval.all_pairs_scores(self.corpus,
                                          engine=self.config.batch_engine,
                                          **self.config.score_kwargs())

    # ---------------------------------------------------------- plumbing
    def precision_at_l(self, labels, top_l: int | None = None, *,
                       scores: Array | None = None) -> float:
        """Corpus-as-queries precision@top-l (paper Section 6).

        ``scores``: precomputed n x n score matrix (e.g. a cached
        ``all_pairs()`` shared across several top-l evaluations, or an
        externally-computed exact matrix); defaults to scoring the corpus
        with this index's configuration.
        """
        top_l = self.config.top_l if top_l is None else top_l
        scores = self.all_pairs() if scores is None else jnp.asarray(scores)
        return retrieval.precision_at_l(scores,
                                        jnp.asarray(np.asarray(labels)),
                                        top_l)

    def recall_at_l(self, other_scores: Array,
                    top_l: int | None = None, *,
                    scores: Array | None = None) -> float:
        """Agreement with a reference ranking: the fraction of
        ``other_scores``' top-l neighbors (per corpus row, self excluded)
        that this index's scoring also retrieves — e.g. cascade-vs-exact
        or LC-bound-vs-EMD agreement, measurable straight from the API.

        ``other_scores``: the reference n x n matrix (exact EMD, a full
        ACT run, ...). ``scores``: this index's precomputed matrix;
        defaults to ``all_pairs()``.
        """
        top_l = self.config.top_l if top_l is None else top_l
        scores = self.all_pairs() if scores is None else jnp.asarray(scores)
        return retrieval.recall_at_l(scores, jnp.asarray(other_scores),
                                     top_l, exclude_self=True)

    def with_config(self, **changes) -> "EmdIndex":
        """Rebuild this index with ``dataclasses.replace``d config. An
        already-built candidate source is reused when the new config
        keeps the same source spec (the expensive host-side fit does not
        rerun for an unrelated knob change)."""
        config = dataclasses.replace(self.config, **changes)
        reuse = (self._source if self._source is not None
                 and config.source_spec == self._source.spec else None)
        return EmdIndex.build(self.corpus, config, mesh=self._mesh,
                              source=reuse)
