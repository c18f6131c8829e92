"""The cascaded prune-and-rescore subsystem (``repro.cascade``).

Covers: spec validation + the static admissibility table, candidate-
compacted scorer parity against the full-corpus engines, the blocked
(ladder-merged) top-k, the API wiring, and the central exactness
property — an admissible cascade whose budgets cover the true top-l
neighbors' stage ranks returns the identical top-l index set as
full-corpus rescoring, for EVERY registered rescorer (the 8-device mesh
version of the same property runs in tests/test_distributed.py).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import cascade
from repro.cascade import (CASCADES, CascadeSpec, CascadeStage, rescore,
                           topk_recall, topk_smallest)
from repro.core import retrieval
from repro.data.synth import make_text_like


@pytest.fixture(scope="module")
def corpus_labels():
    # doc_len < hmax: padded slots on both the corpus and query side.
    return make_text_like(n_docs=40, n_classes=4, vocab=128, m=8,
                          doc_len=10, hmax=16, seed=3)


# ------------------------------------------------------------ spec layer

def test_stage_and_spec_validation():
    with pytest.raises(ValueError, match="unknown cascade stage method"):
        CascadeStage("nope", 8)
    with pytest.raises(ValueError, match="budget"):
        CascadeStage("rwmd", 0)
    with pytest.raises(ValueError, match="budget"):
        CascadeStage("rwmd", 1.5)
    with pytest.raises(ValueError, match="non-increasing"):
        CascadeSpec(stages=(CascadeStage("wcd", 8),
                            CascadeStage("rwmd", 16)))
    with pytest.raises(ValueError, match="at least one"):
        CascadeSpec(stages=())
    with pytest.raises(ValueError, match="unknown rescorer"):
        CascadeSpec(stages=(CascadeStage("rwmd", 8),), rescorer="nope")
    with pytest.raises(ValueError, match="unknown cascade preset"):
        cascade.resolve_spec("nope")
    # hashable (rides inside EngineConfig / keys jit caches)
    spec = CascadeSpec(stages=(CascadeStage("rwmd", 8),))
    assert hash(spec) == hash(CascadeSpec(stages=(CascadeStage("rwmd", 8),)))


def test_admissibility_table():
    lb = cascade.is_lower_bound
    # Theorem-2 chain: RWMD <= OMR <= ACT-k <= ICT <= EMD
    assert lb("rwmd", 0, "omr", 0) and lb("omr", 0, "act", 1)
    assert lb("act", 2, "act", 3) and not lb("act", 3, "act", 2)
    assert lb("act", 3, "ict", 0) and not lb("ict", 0, "act", 3)
    for m in ("rwmd", "omr", "act", "ict", "wcd", "rwmd_rev"):
        assert lb(m, 1, "emd", 0)
        # the fixed-iteration sinkhorn plan is not exactly feasible, so
        # nothing is PROVABLY below it (identity aside)
        assert not lb(m, 1, "sinkhorn", 0)
    assert lb("sinkhorn", 0, "sinkhorn", 0)
    # act with zero rounds degenerates to RWMD
    assert lb("act", 0, "omr", 0)
    # wcd / rwmd_rev / bow are NOT comparable inside the directional chain
    assert not lb("wcd", 0, "act", 3)
    assert not lb("rwmd_rev", 0, "act", 3)
    assert not lb("bow", 0, "emd", 0)
    # every measure bounds itself
    assert lb("wcd", 0, "wcd", 0) and lb("bow", 0, "bow", 0)


def test_presets_valid_and_flagged():
    for name, spec in CASCADES.items():
        assert cascade.resolve_spec(name) is spec
        assert spec.describe()
    assert not CASCADES["fast"].admissible           # wcd vs act rescorer
    assert CASCADES["chain"].admissible
    assert CASCADES["tight"].admissible
    assert CASCADES["exact"].admissible


def test_resolve_budgets_clamps():
    spec = CascadeSpec(stages=(CascadeStage("wcd", 0.5),
                               CascadeStage("rwmd", 0.1)), rescorer="act")
    assert spec.resolve_budgets(100, 4) == (50, 10)
    assert spec.resolve_budgets(100, 30) == (50, 30)    # floor at top_l
    assert spec.resolve_budgets(10, 4) == (5, 4)
    with pytest.raises(ValueError, match="top_l"):
        spec.resolve_budgets(10, 11)
    big = CascadeSpec(stages=(CascadeStage("rwmd", 1000),), rescorer="act")
    assert big.resolve_budgets(64, 4) == (64,)          # cap at n
    # mixed absolute/fractional budgets skip construction-time ordering;
    # a ladder that stops pruning on this corpus errors instead of
    # silently collapsing the later stage
    mixed = CascadeSpec(stages=(CascadeStage("wcd", 10),
                                CascadeStage("rwmd", 0.9)), rescorer="act")
    assert mixed.resolve_budgets(10, 2) == (10, 9)
    with pytest.raises(ValueError, match="non-monotonically"):
        mixed.resolve_budgets(1000, 4)


def test_rescorer_registry():
    names = rescore.names()
    for required in ("act", "ict", "sinkhorn", "emd"):
        assert required in names
    assert rescore.resolve("act").jittable
    assert rescore.resolve("sinkhorn").jittable
    assert not rescore.resolve("emd").jittable          # host-side LP


# ------------------------------------------------- candidate compaction

@pytest.mark.parametrize("method", sorted(
    m for m, s in retrieval.METHODS.items() if s.cand_fn is not None))
def test_cand_scores_match_full_engine(corpus_labels, method):
    """The gather-compacted scorers reproduce the full-corpus batched
    engine at the candidate rows (same per-row reduction order)."""
    c, _ = corpus_labels
    nq, b = 5, 9
    qi, qw = c.ids[:nq], c.w[:nq]
    rng = np.random.default_rng(0)
    cand = jnp.asarray(np.stack([rng.choice(c.n, b, replace=False)
                                 for _ in range(nq)]).astype(np.int32))
    full = np.asarray(retrieval.batch_scores(c, qi, qw, method=method,
                                             iters=2, block_q=2))
    got = np.asarray(retrieval.cand_scores(c, qi, qw, cand, method=method,
                                           iters=2, block_q=2))
    want = np.take_along_axis(full, np.asarray(cand), axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_cand_scores_rejects_methods_without_cand_fn(corpus_labels,
                                                     monkeypatch):
    c, _ = corpus_labels
    gutted = dataclasses.replace(retrieval.METHODS["act"], cand_fn=None)
    monkeypatch.setitem(retrieval.METHODS, "gutted", gutted)
    with pytest.raises(ValueError, match="candidate-compacted"):
        retrieval.cand_scores(c, c.ids[:2], c.w[:2],
                              jnp.zeros((2, 3), jnp.int32), method="gutted")


def test_ict_registered_and_chain_position(corpus_labels):
    """Satellite: ict is a registry method and Theorem 2 holds for the
    batch engines on real (padded) corpus rows."""
    c, _ = corpus_labels
    assert "ict" in retrieval.METHODS
    qi, qw = c.ids[:4], c.w[:4]
    chain = [np.asarray(retrieval.batch_scores(c, qi, qw, method=m,
                                               iters=it))
             for m, it in (("rwmd", 0), ("omr", 0), ("act", 1),
                           ("act", 3), ("ict", 0))]
    for lo, hi in zip(chain, chain[1:], strict=False):
        assert (lo <= hi + 1e-5).all()


# ------------------------------------------------------- blocked top-k

@pytest.mark.parametrize("blocks", [1, 2, 4, 8])
def test_blocked_topk_matches_plain(blocks):
    rng = np.random.default_rng(1)
    s = jnp.asarray(rng.normal(size=(5, 64)).astype(np.float32))
    v0, i0 = topk_smallest(s, 7)
    v, i = topk_smallest(s, 7, blocks=blocks)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v0))
    np.testing.assert_array_equal(np.sort(np.asarray(i), 1),
                                  np.sort(np.asarray(i0), 1))


def test_blocked_topk_uneven_split_falls_back():
    s = jnp.asarray(np.random.default_rng(2).normal(size=(3, 50)),
                    jnp.float32)
    v0, i0 = topk_smallest(s, 5)
    v, i = topk_smallest(s, 5, blocks=4)          # 50 % 4 != 0
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i0))
    np.testing.assert_allclose(np.asarray(v), np.asarray(v0))


def _check_topk_ties(s, k, blocks):
    """Tie-breaking contract of ``topk_smallest`` on duplicate scores:
    blocks=1 must match ``lax.top_k`` EXACTLY (values and indices — the
    lowest index wins ties); the blocked ladder merge returns the same
    values with a score-consistent, duplicate-free index set (its ties
    resolve by (block, local rank) — a recall-silent difference, pinned
    here so a silent regression cannot slip in)."""
    import jax

    neg, ref_i = jax.lax.top_k(-s, k)
    v, i = topk_smallest(s, k, blocks=blocks)
    np.testing.assert_array_equal(np.asarray(v), -np.asarray(neg))
    iv = np.asarray(i)
    if blocks == 1:
        np.testing.assert_array_equal(iv, np.asarray(ref_i))
        return
    # every selected index carries exactly its reported score, no index
    # is selected twice, and the multiset of scores matches lax.top_k's
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(s), iv, 1),
                                  np.asarray(v))
    assert all(len(set(row)) == len(row) for row in iv)


@pytest.mark.parametrize("blocks", [1, 2, 4])
def test_topk_smallest_tie_breaking_fixed_seeds(blocks):
    """Satellite: duplicate-heavy scores (integers in a tiny range) hit
    tie-breaking on every row."""
    for seed in (0, 1, 2):
        rng = np.random.default_rng(seed)
        s = jnp.asarray(rng.integers(0, 4, size=(6, 32)), jnp.float32)
        _check_topk_ties(s, 7, blocks)


def test_topk_smallest_tie_breaking_property():
    """Hypothesis sweep of the tie-breaking contract."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None, derandomize=True)
    @given(seed=st.integers(0, 2**31 - 1), rows=st.integers(1, 5),
           n=st.sampled_from([16, 32, 48]), k=st.integers(1, 9),
           spread=st.integers(1, 5), blocks=st.sampled_from([1, 2, 4, 8]))
    def run(seed, rows, n, k, spread, blocks):
        rng = np.random.default_rng(seed)
        s = jnp.asarray(rng.integers(0, spread, size=(rows, n)),
                        jnp.float32)
        _check_topk_ties(s, k, blocks)

    run()


def test_topk_recall():
    a = np.array([[0, 1, 2], [3, 4, 5]])
    assert topk_recall(a, a) == 1.0
    assert topk_recall(a, np.array([[0, 1, 9], [3, 4, 9]])) == \
        pytest.approx(2 / 3)
    with pytest.raises(ValueError, match="shape"):
        topk_recall(a, a[:, :2])


# ------------------------------------------------- exactness property

def _rank_budgets(stage_scores, ref_idx, top_l):
    """Smallest budget per stage that keeps every reference top-l item:
    1 + the worst stable-sort rank of any reference item, maxed over
    queries (matches lax.top_k's lowest-index tie rule)."""
    budgets = []
    for s in stage_scores:
        order = np.argsort(s, axis=1, kind="stable")
        rank = np.empty_like(order)
        np.put_along_axis(rank, order, np.arange(s.shape[1])[None, :],
                          axis=1)
        need = int(np.take_along_axis(rank, ref_idx, axis=1).max()) + 1
        budgets.append(max(top_l, need))
    # budgets must be non-increasing along the ladder
    for i in range(len(budgets) - 2, -1, -1):
        budgets[i] = max(budgets[i], budgets[i + 1])
    return budgets


#: Admissible stage ladder for each registered rescorer (a measure always
#: bounds itself; the chain/EMD relations cover the rest).
_ADMISSIBLE_STAGES = {
    "act": (("rwmd", 0), ("omr", 0)),
    "ict": (("rwmd", 0), ("act", 1)),
    "omr": (("rwmd", 0),),
    "rwmd": (("rwmd", 0),),
    "rwmd_rev": (("rwmd_rev", 0),),
    "bow": (("bow", 0),),
    "wcd": (("wcd", 0),),
    "sinkhorn": (("wcd", 0), ("rwmd", 0)),
    "emd": (("wcd", 0), ("rwmd", 0)),
}


def _full_rescorer_scores(c, qi, qw, rescorer, iters, use_kernels=False):
    """Full-corpus scores THROUGH the rescorer's own candidate scorer
    (cand = every row), so the cascade and the reference share float
    behavior exactly."""
    nq = qi.shape[0]
    all_rows = jnp.broadcast_to(jnp.arange(c.n, dtype=jnp.int32),
                                (nq, c.n))
    r = rescore.resolve(rescorer)
    if r.jittable:
        return np.asarray(r.fn(c, qi, qw, all_rows, iters=iters,
                               use_kernels=use_kernels))
    return np.asarray(r.host_fn(c, qi, qw, np.asarray(all_rows)))


def _check_admissible_exactness(rescorer: str, seed: int,
                                use_kernels: bool = False):
    """One instance of the acceptance property: an admissible cascade
    (every stage a provable lower bound of the rescorer, budgets >= top_l
    and >= the stage-score rank of every true top-l neighbor) returns the
    identical top-l index set as full-corpus rescoring.
    ``use_kernels`` runs the SAME property with the fused candidate
    kernels (interpret mode) in every stage and the rescorer — budgets
    and the reference ranking are derived from the kernel path's own
    scores, so coverage holds on the path under test."""
    c, _ = make_text_like(n_docs=20, n_classes=3, vocab=64, m=6,
                          doc_len=8, hmax=8, seed=seed)
    nq, top_l = 3, 3
    qi, qw = c.ids[:nq], c.w[:nq]
    iters = 2 if rescorer == "act" else 1
    full = _full_rescorer_scores(c, qi, qw, rescorer, iters, use_kernels)
    ref_idx = np.argsort(full, axis=1, kind="stable")[:, :top_l]

    stages = _ADMISSIBLE_STAGES[rescorer]
    stage_scores = [np.asarray(retrieval.batch_scores(
        c, qi, qw, method=m, iters=it, use_kernels=use_kernels))
        for m, it in stages]
    budgets = _rank_budgets(stage_scores, ref_idx, top_l)
    spec = CascadeSpec(
        stages=tuple(CascadeStage(m, b, iters=it)
                     for (m, it), b in zip(stages, budgets, strict=True)),
        rescorer=rescorer, rescorer_iters=iters)
    # sinkhorn is deliberately outside the provable table (its
    # fixed-iteration plan is not exactly feasible); rank-covering
    # budgets still make the cascade exact by construction
    assert spec.admissible == (rescorer != "sinkhorn"), spec.describe()

    res = cascade.cascade_search(c, qi, qw, spec, top_l,
                                 use_kernels=use_kernels)
    got = np.sort(np.asarray(res.indices), axis=1)
    assert got.shape == (nq, top_l)
    np.testing.assert_array_equal(got, np.sort(ref_idx, axis=1),
                                  err_msg=spec.describe())


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["reference", "kernels"])
@pytest.mark.parametrize("rescorer", sorted(_ADMISSIBLE_STAGES))
def test_admissible_cascade_exact_fixed_seeds(rescorer, use_kernels):
    """The acceptance property on pinned seeds (always runs, even where
    hypothesis is unavailable) — every registered rescorer, on the
    reference path AND composed with the fused candidate kernels. Seed
    59 ties two bow rescores at the top-l boundary: the cascade must
    break the tie by row id, as full-corpus search does."""
    for seed in (3, 17, 59):
        _check_admissible_exactness(rescorer, seed, use_kernels)


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["reference", "kernels"])
@pytest.mark.parametrize("rescorer", sorted(_ADMISSIBLE_STAGES))
def test_admissible_cascade_exact_property(rescorer, use_kernels):
    """Hypothesis sweep of the same property over random corpora, for
    every admissible ladder with and without the fused kernels."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=4, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def run(seed):
        _check_admissible_exactness(rescorer, seed, use_kernels)

    run()


def test_cascade_kernel_path_matches_reference_path(corpus_labels):
    """Acceptance: an admissible cascade whose budgets cover the true
    top-l stage ranks under BOTH paths returns the identical top-l set
    with use_kernels=True and False, and the rescorer scores of that set
    agree to the last ulps (the fused kernels reuse the reference
    reductions — see kernels/cand_pour)."""
    c, _ = corpus_labels
    nq, top_l, iters = 4, 4, 2
    qi, qw = c.ids[:nq], c.w[:nq]
    stages = (("rwmd", 0), ("omr", 0))
    results = {}
    for uk in (False, True):
        full = _full_rescorer_scores(c, qi, qw, "act", iters, uk)
        ref_idx = np.argsort(full, axis=1, kind="stable")[:, :top_l]
        ss = [np.asarray(retrieval.batch_scores(c, qi, qw, method=m,
                                                iters=it, use_kernels=uk))
              for m, it in stages]
        results[uk] = (_rank_budgets(ss, ref_idx, top_l), ref_idx)
    budgets = [max(a, b) for a, b in zip(results[False][0],
                                         results[True][0], strict=True)]
    spec = CascadeSpec(stages=tuple(CascadeStage(m, b, iters=it)
                                    for (m, it), b in zip(stages, budgets,
                                                          strict=True)),
                       rescorer="act", rescorer_iters=iters)
    assert spec.admissible
    res_r = cascade.cascade_search(c, qi, qw, spec, top_l)
    res_k = cascade.cascade_search(c, qi, qw, spec, top_l,
                                   use_kernels=True)
    order_r = np.argsort(np.asarray(res_r.indices), axis=1)
    order_k = np.argsort(np.asarray(res_k.indices), axis=1)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(res_r.indices), order_r, 1),
        np.take_along_axis(np.asarray(res_k.indices), order_k, 1))
    s_r = np.take_along_axis(np.asarray(res_r.scores), order_r, 1)
    s_k = np.take_along_axis(np.asarray(res_k.scores), order_k, 1)
    from test_cand_kernels import assert_ulp_equal
    assert_ulp_equal(s_k, s_r, err_msg="cascade kernel-vs-reference")


def test_emdindex_pallas_backend_cascade(corpus_labels):
    """EngineConfig(backend="pallas", cascade=...) reaches the fused
    candidate kernels through the API and agrees with the reference
    backend at generous budgets."""
    import dataclasses as dc

    from repro.api import EmdIndex, EngineConfig
    c, _ = corpus_labels
    qi, qw = c.ids[:5], c.w[:5]
    spec = CascadeSpec(stages=(CascadeStage("rwmd", 24),
                               CascadeStage("omr", 12)),
                       rescorer="act", rescorer_iters=2)
    cfg = EngineConfig(method="act", iters=2, top_l=4, cascade=spec,
                       backend="pallas")
    s_k, i_k = EmdIndex.build(c, cfg).search(qi, qw)
    ref = EmdIndex.build(c, dc.replace(cfg, backend="reference"))
    s_r, i_r = ref.search(qi, qw)
    np.testing.assert_array_equal(np.sort(np.asarray(i_k), 1),
                                  np.sort(np.asarray(i_r), 1))
    np.testing.assert_allclose(np.sort(np.asarray(s_k), 1),
                               np.sort(np.asarray(s_r), 1),
                               rtol=1e-5, atol=1e-6)


def test_full_budget_cascade_bitwise_exact(corpus_labels):
    """budget == n degenerates to full-corpus rescoring: identical
    indices AND scores."""
    c, _ = corpus_labels
    qi, qw = c.ids[:4], c.w[:4]
    spec = CascadeSpec(stages=(CascadeStage("rwmd", c.n),),
                       rescorer="act", rescorer_iters=2)
    res = cascade.cascade_search(c, qi, qw, spec, 5)
    full = retrieval.batch_scores(c, qi, qw, method="act", iters=2)
    v, i = topk_smallest(full, 5)
    np.testing.assert_array_equal(np.asarray(res.indices), np.asarray(i))
    np.testing.assert_allclose(np.asarray(res.scores), np.asarray(v),
                               rtol=1e-6, atol=1e-7)


def test_cascade_masks_pad_rows(corpus_labels):
    """n_valid: zero-weight pad rows (which score 0 = best for LC
    methods) never enter candidacy."""
    c, _ = corpus_labels
    from repro.core.lc import Corpus
    padded = Corpus(ids=jnp.pad(c.ids, ((0, 8), (0, 0))),
                    w=jnp.pad(c.w, ((0, 8), (0, 0))), coords=c.coords)
    spec = CascadeSpec(stages=(CascadeStage("rwmd", 16),),
                       rescorer="act", rescorer_iters=1)
    res = cascade.cascade_search(padded, c.ids[:4], c.w[:4], spec, 6,
                                 n_valid=c.n)
    assert int(np.asarray(res.indices).max()) < c.n


def test_stage_rows_strictly_fewer_candidates(corpus_labels):
    """The budget ladder: every post-prefetch stage reads strictly fewer
    rows than full-corpus scoring (the bench's row-count claim)."""
    spec = CASCADES["fast"]
    rows = cascade.stage_rows(spec, 1000, 16)
    assert rows == {"stage1.wcd": 1000, "stage2.rwmd": 400,
                    "rescore.act": 50}
    assert sum(v for k, v in rows.items()
               if not k.startswith("stage1")) < 1000


# ------------------------------------------------------------ API layer

def test_emdindex_cascade_config_and_adhoc(corpus_labels):
    from repro.api import EmdIndex, EngineConfig
    c, _ = corpus_labels
    qi, qw = c.ids[:5], c.w[:5]
    spec = CascadeSpec(stages=(CascadeStage("rwmd", 24),
                               CascadeStage("omr", 12)),
                       rescorer="act", rescorer_iters=2)
    via_config = EmdIndex.build(c, EngineConfig(method="act", iters=2,
                                                top_l=4, cascade=spec))
    s, i = via_config.search(qi, qw)
    assert s.shape == (5, 4) and i.shape == (5, 4)
    plain = EmdIndex.build(c, EngineConfig(method="act", iters=2, top_l=4))
    s2, i2 = plain.search(qi, qw, cascade=spec)       # ad-hoc spec
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s2), rtol=1e-6)
    # single query keeps the uniform shape contract
    s1, i1 = via_config.search(c.ids[0], c.w[0])
    assert s1.shape == (4,) and i1.shape == (4,)
    np.testing.assert_array_equal(
        np.asarray(i1), np.asarray(via_config.search(c.ids[:1],
                                                     c.w[:1])[1][0]))
    # generous budgets here => the cascade agrees with full search
    _, i_full = plain.search(qi, qw)
    assert topk_recall(i, i_full) == 1.0
    # the per-call escape hatch honors the same symmetric/cascade
    # incompatibility EngineConfig enforces
    sym = EmdIndex.build(c, EngineConfig(method="rwmd", symmetric=True))
    with pytest.raises(ValueError, match="symmetric"):
        sym.search(qi, qw, cascade="fast")


def test_emdindex_cascade_distributed_single_device(corpus_labels):
    import dataclasses as dc

    from repro.api import EmdIndex, EngineConfig
    c, _ = corpus_labels
    qi, qw = c.ids[:5], c.w[:5]
    spec = CascadeSpec(stages=(CascadeStage("rwmd", 24),
                               CascadeStage("omr", 12)),
                       rescorer="act", rescorer_iters=2)
    cfg = EngineConfig(method="act", iters=2, top_l=4, cascade=spec,
                       backend="distributed", pad_multiple=16, block_q=3)
    dst = EmdIndex.build(c, cfg)
    assert dst._padded_corpus.n > c.n                 # pad rows in play
    ref = EmdIndex.build(c, dc.replace(cfg, backend="reference"))
    s_d, i_d = dst.search(qi, qw)
    s_r, i_r = ref.search(qi, qw)
    np.testing.assert_array_equal(np.asarray(i_d), np.asarray(i_r))
    np.testing.assert_allclose(np.asarray(s_d), np.asarray(s_r),
                               rtol=1e-5, atol=1e-6)
    assert int(np.asarray(i_d).max()) < c.n           # pads masked
    with pytest.raises(ValueError, match="baked at build time"):
        dst.search(qi, qw, cascade="fast")
    with pytest.raises(ValueError, match="top_l"):
        dst.search(qi, qw, top_l=7)


def test_engine_config_cascade_validation():
    from repro.api import EngineConfig
    with pytest.raises(ValueError, match="unknown cascade preset"):
        EngineConfig(cascade="nope")
    with pytest.raises(ValueError, match="symmetric"):
        EngineConfig(method="rwmd", symmetric=True, cascade="fast")
    with pytest.raises(ValueError, match="host"):
        EngineConfig(backend="distributed", cascade="exact")
    cfg = EngineConfig(cascade="fast")
    assert cfg.cascade_spec is CASCADES["fast"]
    assert hash(cfg) == hash(EngineConfig(cascade="fast"))


def test_precision_and_recall_accept_precomputed_scores(corpus_labels):
    """Satellite: precision_at_l takes precomputed scores; recall_at_l
    measures cascade-vs-exact style agreement from the API."""
    from repro.api import EmdIndex, EngineConfig
    c, labels = corpus_labels
    index = EmdIndex.build(c, EngineConfig(method="act", iters=2))
    S = index.all_pairs()
    assert index.precision_at_l(labels, 4) == \
        index.precision_at_l(labels, 4, scores=S)
    assert index.recall_at_l(S, 4) == 1.0
    assert index.recall_at_l(S, 4, scores=S) == 1.0
    # a looser bound's ranking agrees only partially with the tight one
    loose = EmdIndex.build(c, EngineConfig(method="wcd"))
    r = loose.recall_at_l(S, 4)
    assert 0.0 < r <= 1.0
    with pytest.raises(ValueError, match="shape"):
        retrieval.recall_at_l(S, S[:, :3], 4)
