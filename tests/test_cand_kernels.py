"""Kernel-conformance harness for the fused candidate-compaction kernels.

The contract (see ``kernels/cand_pour``'s module docstring):

* both gathers are BITWISE equal to an XLA gather: the ladder modes'
  (pour, omr) hand the kernel ``table[:, ids]`` itself, the one-hot
  modes' (rev_min, ict) in-kernel one-hot gather reproduces it;
* every fused candidate kernel matches its XLA-gather oracle and the
  reference ``lc_*_scores_cand`` engine to within ``ULP_TOL`` (4) float32
  ulps — the kernels reuse the reference reduction formulas on
  identically shaped tiles, so the residual ulps come from XLA re-fusing
  the REFERENCE path per program (FMA contraction of its reductions),
  not from the kernels;
* the LC-ICT remainder dump stays at the max FINITE cost under the
  kernel path (a PAD_DIST dump would explode float residue by ~1e30).

Sweeps pad rows, duplicate candidate ids, budgets not divisible by the
candidate block, and nq=1 vs batched grids — fixed cases plus a
hypothesis property (derandomized, so CI is deterministic).
"""
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lc, retrieval
from repro.core.lc import PAD_DIST, Corpus
from repro.data.synth import make_text_like
from repro.kernels import ops as kops
from repro.kernels import ref as kref

#: Every registry method with a fused candidate kernel path.
CAND_METHODS = ("rwmd", "rwmd_rev", "omr", "act", "ict")

#: Max float32 ulp distance the conformance suite tolerates: the bound on
#: the reference path's per-program reduction reassociation (the kernels'
#: outputs are themselves deterministic across programs).
ULP_TOL = 4


def _ordered(f):
    """Map float32 bits to integers whose differences count ulps
    (negative floats mirror below zero; -0.0 and +0.0 both map to 0)."""
    i = np.ascontiguousarray(np.asarray(f, np.float32)).view(np.int32)
    i = i.astype(np.int64)
    return np.where(i >= 0, i, np.int64(-2**31) - i)


def assert_ulp_equal(got, want, max_ulp=ULP_TOL, err_msg=""):
    """Exact equality up to ``max_ulp`` float32 ulps (0 distance for
    bit-identical values; the default covers the reference path's
    per-program fusion wobble — see the module docstring)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    ulp = np.abs(_ordered(got) - _ordered(want))
    assert ulp.max(initial=0) <= max_ulp, (
        f"{err_msg}: {int((ulp > max_ulp).sum())}/{ulp.size} entries "
        f"beyond {max_ulp} ulp (max {int(ulp.max())}); "
        f"max abs diff {np.abs(got - want).max()}")


def _pad_corpus(c, pad_rows: int) -> Corpus:
    """Append zero-weight pad rows (id 0), as the distributed layouts do."""
    if not pad_rows:
        return c
    return Corpus(ids=jnp.pad(c.ids, ((0, pad_rows), (0, 0))),
                  w=jnp.pad(c.w, ((0, pad_rows), (0, 0))), coords=c.coords)


def _random_cand(rng, n, nq, b, duplicates=False, include=None):
    """(nq, b) candidate ids; ``duplicates`` samples with replacement,
    ``include`` forces specific row ids into every query's set."""
    cand = np.stack([rng.choice(n, b, replace=duplicates)
                     for _ in range(nq)])
    if include is not None:
        cand[:, :len(include)] = include
    return jnp.asarray(cand.astype(np.int32))


def _check_all_methods(c, qi, qw, cand, *, iters=2, block_q=8, block_n=128,
                       block_v=256, label=""):
    for method in CAND_METHODS:
        ref_s = retrieval.cand_scores(c, qi, qw, cand, method=method,
                                      iters=iters, block_q=block_q)
        ker_s = retrieval.cand_scores(c, qi, qw, cand, method=method,
                                      iters=iters, block_q=block_q,
                                      use_kernels=True, block_n=block_n,
                                      block_v=block_v)
        assert_ulp_equal(ker_s, ref_s, err_msg=f"{label}:{method}")


# ------------------------------------------------ engine-level conformance

@pytest.fixture(scope="module")
def corpus():
    return make_text_like(n_docs=40, n_classes=4, vocab=128, m=8,
                          doc_len=10, hmax=16, seed=3)[0]


_CASES = {
    # name: (nq, b, block_n, block_v, block_q, duplicates, pad_rows)
    "batched": (5, 13, 8, 32, 2, False, 0),
    "nq1": (1, 9, 16, 256, 8, False, 0),
    "duplicate_cands": (4, 12, 8, 64, 8, True, 0),
    "pad_rows_in_cand": (3, 10, 8, 128, 2, False, 8),
    "budget_not_block_multiple": (3, 21, 8, 16, 8, False, 0),
    "one_block": (2, 8, 128, 256, 8, False, 0),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_cand_engines_match_reference(corpus, case):
    """Fused kernels vs the reference candidate engines, all five
    methods, across the pad/duplicate/blocking sweep."""
    nq, b, block_n, block_v, block_q, dup, pad_rows = _CASES[case]
    c = _pad_corpus(corpus, pad_rows)
    # crc32, not hash(): Python's string hash is salted per process, which
    # would make these "fixed" cases draw fresh candidates every run
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    # pad rows (if any) are forced INTO the candidate sets: a candidate
    # kernel must score them exactly like the reference (zero weight
    # rows pour nothing), not merely never see them.
    include = [c.n - 1, c.n - 2] if pad_rows else None
    cand = _random_cand(rng, c.n, nq, b, duplicates=dup, include=include)
    qi, qw = corpus.ids[:nq], corpus.w[:nq]
    _check_all_methods(c, qi, qw, cand, block_q=block_q, block_n=block_n,
                       block_v=block_v, label=case)


def test_cand_engines_property():
    """Hypothesis sweep of the same conformance over random corpora,
    candidate sets, and block shapes (derandomized: CI-deterministic)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=5, deadline=None, derandomize=True)
    @given(seed=st.integers(0, 2**31 - 1), nq=st.integers(1, 5),
           b=st.integers(1, 24), block_n=st.sampled_from([8, 16, 128]),
           block_v=st.sampled_from([16, 64, 256]),
           duplicates=st.booleans(), pad=st.booleans())
    def run(seed, nq, b, block_n, block_v, duplicates, pad):
        c0, _ = make_text_like(n_docs=24, n_classes=3, vocab=64, m=6,
                               doc_len=8, hmax=8, seed=seed)
        c = _pad_corpus(c0, 8 if pad else 0)
        rng = np.random.default_rng(seed)
        b_ = min(b, c.n)
        cand = _random_cand(rng, c.n, nq, b_, duplicates=duplicates)
        _check_all_methods(c, c0.ids[:nq], c0.w[:nq], cand, block_q=2,
                           block_n=block_n, block_v=block_v,
                           label=f"seed{seed}")

    run()


# --------------------------------------------------- ops-level conformance

def _handoff(rng, nq, v, k, iters):
    Z = jnp.asarray(np.sort(rng.uniform(size=(nq, v, k)), -1), jnp.float32)
    W = jnp.asarray(rng.uniform(size=(nq, v, max(iters, 1))) * 0.3,
                    jnp.float32)
    return Z, W


def _cand_inputs(rng, nq, b, hmax, v):
    idsg = jnp.asarray(rng.integers(0, v, (nq, b, hmax)), jnp.int32)
    xg = jnp.asarray(rng.uniform(size=(nq, b, hmax)) *
                     (rng.uniform(size=(nq, b, hmax)) > 0.3), jnp.float32)
    return idsg, xg


@pytest.mark.parametrize("nq,b,hmax,v,iters", [
    (1, 9, 7, 37, 0), (3, 13, 7, 37, 3), (2, 8, 16, 128, 1),
    (4, 30, 5, 64, 7),
])
def test_cand_pour_op_matches_oracle(nq, b, hmax, v, iters, rng):
    idsg, xg = _cand_inputs(rng, nq, b, hmax, v)
    Z, W = _handoff(rng, nq, v, iters + 1, iters)
    got = kops.cand_pour(idsg, xg, Z, None if iters == 0 else W, iters,
                         block_n=8)
    want = kref.cand_pour_ref(idsg, xg, Z, None if iters == 0 else W, iters)
    assert_ulp_equal(got, want, err_msg=f"pour it={iters}")


@pytest.mark.parametrize("nq,b,hmax,v", [(1, 9, 7, 37), (3, 13, 9, 64)])
def test_cand_omr_op_matches_oracle(nq, b, hmax, v, rng):
    idsg, xg = _cand_inputs(rng, nq, b, hmax, v)
    Z, W = _handoff(rng, nq, v, 2, 1)
    # exact-zero nearest costs exercise the overlap branch
    Z = Z.at[:, ::3, 0].set(0.0)
    got = kops.cand_omr(idsg, xg, Z, W[..., 0], block_n=8)
    want = kref.cand_omr_ref(idsg, xg, Z, W[..., 0])
    assert_ulp_equal(got, want, err_msg="omr")


@pytest.mark.parametrize("mode", ["rev_min", "ict"])
@pytest.mark.parametrize("nq,b,hmax,v,h", [(1, 9, 7, 37, 6),
                                           (3, 13, 5, 64, 10)])
def test_cand_dist_ops_match_oracle(mode, nq, b, hmax, v, h, rng):
    idsg, xg = _cand_inputs(rng, nq, b, hmax, v)
    Dq = jnp.asarray(rng.uniform(size=(nq, v, h)), jnp.float32)
    qw = jnp.asarray(rng.uniform(size=(nq, h)), jnp.float32)
    # a padded query bin per query: PAD_DIST cost column, zero weight
    Dq = Dq.at[:, :, -1].set(PAD_DIST)
    qw = qw.at[:, -1].set(0.0)
    op = kops.cand_rev_min if mode == "rev_min" else kops.cand_ict
    oracle = (kref.cand_rev_min_ref if mode == "rev_min"
              else kref.cand_ict_ref)
    got = op(idsg, xg, Dq, qw, block_n=8, block_v=16)
    assert_ulp_equal(got, oracle(idsg, xg, Dq, qw), err_msg=mode)


def test_cand_gather_is_bitwise_exact(rng):
    """The in-kernel one-hot gather reproduces an XLA gather bit-for-bit:
    the bfloat16 one-hot meets the exact bfloat16 split of the float32
    table, so every gathered value is 1.0 * part + exact zeros — the
    structural half of the conformance contract."""
    import jax
    from jax.experimental import pallas as pl

    from repro.kernels.cand_pour import gather_slab, split_bf16

    v, width, hp = 48, 5, 128
    table = jnp.asarray(rng.uniform(size=(width, v)) *
                        np.where(rng.uniform(size=(width, v)) > 0.9,
                                 PAD_DIST, 1.0), jnp.float32)
    ids = jnp.asarray(rng.integers(0, v, (1, hp)), jnp.int32)
    parts = split_bf16(table).reshape(1, -1, v)          # (1, 3 * width, v)

    def kernel(ids_ref, tab_ref, out_ref):
        out_ref[...] = gather_slab(tab_ref, ids_ref[...], 3, width,
                                   jnp.zeros((width, hp), jnp.float32))

    got = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec((1, hp), lambda: (0, 0)),
                  pl.BlockSpec((1, 3 * width, v), lambda: (0, 0, 0))],
        out_specs=pl.BlockSpec((width, hp), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((width, hp), jnp.float32),
        interpret=True,
    )(ids, parts)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(table[:, ids[0]]))


# ------------------------------------------- the two gathers, by mode

def _pallas_grids(fn, *args):
    """Grids of every ``pallas_call`` in ``fn``'s jaxpr, nested ones
    (jit, ``lax.map``) included."""
    import jax

    grids = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                grids.append(tuple(e.params["grid_mapping"].grid))
            for p in e.params.values():
                for sub in p if isinstance(p, (list, tuple)) else [p]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


@pytest.mark.parametrize("mode,iters", [("pour", 0), ("pour", 3),
                                        ("omr", 0)])
def test_cand_ladder_gather_is_table_at_ids(monkeypatch, rng, mode, iters):
    """The ladder modes hand the kernel each slot's rungs gathered by id:
    ``table[:, ids]`` of the query's row-major table, bit for bit, pad
    slots (id 0) and pad candidate rows included."""
    nq, b, hmax, v, block_n = 2, 13, 7, 37, 8
    idsg, xg = _cand_inputs(rng, nq, b, hmax, v)
    Z, W = _handoff(rng, nq, v, iters + 2, max(iters, 1))
    handed = []

    def spy(x, g, *args, **kw):
        handed.append(np.asarray(g))
        return jnp.zeros(x.shape[:2] + (1,), jnp.float32)

    monkeypatch.setattr(kops, "cand_pallas", spy)
    if mode == "omr":
        kops.cand_omr.__wrapped__(idsg, xg, Z, W[..., 0], block_n=block_n)
        cols = [Z[..., 0], Z[..., 1], W[..., 0]]
    else:
        kops.cand_pour.__wrapped__(idsg, xg, Z, W if iters else None, iters,
                                   block_n=block_n)
        Wf = W[..., :iters]
        cols = ([Z[..., l] for l in range(iters + 1)]
                + [Wf[..., l] for l in range(iters)]
                + [(jnp.cumsum(Wf, axis=-1) - Wf)[..., l]
                   for l in range(iters)])
    table = np.asarray(jnp.stack(cols, axis=1))          # (nq, rows, v)
    ids = np.zeros((nq, 16, 128), np.int32)              # padded as launched
    ids[:, :b, :hmax] = np.asarray(idsg)
    (got,) = handed
    want = np.stack([table[q][:, ids[q]] for q in range(nq)])
    assert got.shape == (nq, len(cols), 16, 128)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("mode", ["pour", "omr"])
def test_cand_ladder_grid_has_no_vocabulary_axis(rng, mode):
    """The ladder modes' launch is a (query, candidate block) grid: the
    same at v = 48 and v = 4,800 for the same b and hmax."""
    nq, b, hmax = 2, 24, 7
    grids = []
    for v in (48, 4800):
        idsg, xg = _cand_inputs(rng, nq, b, hmax, v)
        Z, W = _handoff(rng, nq, v, 4, 3)
        if mode == "omr":
            grids.append(_pallas_grids(
                lambda i, x, z, w: kops.cand_omr(i, x, z, w, block_n=8),
                idsg, xg, Z, W[..., 0]))
        else:
            grids.append(_pallas_grids(
                lambda i, x, z, w: kops.cand_pour(i, x, z, w, 3, block_n=8),
                idsg, xg, Z, W))
    assert grids[0] == grids[1] == [(nq, b // 8)]


@pytest.mark.parametrize("mode", ["rev_min", "ict"])
def test_cand_dist_modes_keep_the_onehot_gather(rng, mode):
    """Reverse RWMD and ICT keep the in-kernel one-hot gather: their grid
    streams the vocabulary in ``block_v`` slabs, and their scores match
    the XLA-gather oracle."""
    nq, b, hmax, h = 2, 13, 7, 6
    op = kops.cand_rev_min if mode == "rev_min" else kops.cand_ict
    oracle = (kref.cand_rev_min_ref if mode == "rev_min"
              else kref.cand_ict_ref)
    for v, slabs in ((48, 3), (4800, 300)):
        idsg, xg = _cand_inputs(rng, nq, b, hmax, v)
        Dq = jnp.asarray(rng.uniform(size=(nq, v, h)), jnp.float32)
        qw = jnp.asarray(rng.uniform(size=(nq, h)), jnp.float32)

        def run(i, x, d, w):
            return op(i, x, d, w, block_n=8, block_v=16)
        assert _pallas_grids(run, idsg, xg, Dq, qw) == [(nq, 2, slabs)]
        assert_ulp_equal(run(idsg, xg, Dq, qw), oracle(idsg, xg, Dq, qw),
                         err_msg=f"{mode} v={v}")


@pytest.mark.parametrize("mode", ["pour", "omr"])
def test_cand_ladder_chunks_over_the_gather_cap(monkeypatch, rng, mode):
    """Over ``GATHER_CAP_BYTES`` the ladder is gathered and reduced in
    chunks of whole candidate blocks, with the unchunked scores."""
    import jax

    nq, b, hmax, v, iters = 2, 45, 7, 37, 3
    idsg, xg = _cand_inputs(rng, nq, b, hmax, v)
    Z, W = _handoff(rng, nq, v, iters + 1, iters)
    if mode == "omr":
        def run(i, x, z, w):
            return kops.cand_omr(i, x, z, w[..., 0], block_n=8)
    else:
        def run(i, x, z, w):
            return kops.cand_pour(i, x, z, w, iters, block_n=8)
    args = (idsg, xg, Z, W)
    want = np.asarray(run(*args))
    rows = 3 if mode == "omr" else 1 + 3 * iters
    # room for two blocks of 8 candidate rows of 128 slots
    cap = 2 * nq * rows * 8 * 128 * 4
    monkeypatch.setattr(kops, "GATHER_CAP_BYTES", cap)
    jax.clear_caches()
    try:
        assert _pallas_grids(run, *args) == [(nq, 2)]   # 48 rows, 3 chunks
        got = np.asarray(run(*args))
    finally:
        jax.clear_caches()
    np.testing.assert_array_equal(got, want)


# --------------------------------------------- ict remainder-dump contract

def test_cand_ict_remainder_dump_stays_max_finite():
    """Regression (cascade satellite): an all-remainder query — total
    capacity far below the row's mass — must dump the residue at the max
    FINITE gathered cost under the kernel path too. A PAD_DIST dump
    would score ~1e30 * remainder instead of ~1."""
    idsg = jnp.zeros((1, 1, 1), jnp.int32)
    xg = jnp.ones((1, 1, 1), jnp.float32)
    # one real query bin at cost 1.0 with capacity 0.25; one padded bin
    Dq = jnp.asarray([[[1.0, PAD_DIST]]], jnp.float32)
    qw = jnp.asarray([[0.25, 0.0]], jnp.float32)
    got = np.asarray(kops.cand_ict(idsg, xg, Dq, qw))
    # 0.25 poured at cost 1.0 + 0.75 remainder dumped at max finite (1.0)
    np.testing.assert_allclose(got, [[1.0]], rtol=1e-6)
    assert got[0, 0] < 1e6, "remainder was dumped at PAD_DIST"
    np.testing.assert_array_equal(got,
                                  np.asarray(kref.cand_ict_ref(idsg, xg,
                                                               Dq, qw)))


# ------------------------------------------ precision-policy conformance

#: Measured max absolute error of each reduced-precision policy against
#: the float32 engines on the conformance fixture (kernel and reference
#: paths; 3x headroom over the observed worst case — bf16 storage error
#: peaked at 2.2e-3 and bf16_agg's bf16 matmul at 1.3e-1, on omr).
POLICY_ABS_TOL = {"bf16": 8e-3, "bf16_agg": 0.4}


@pytest.mark.parametrize("policy", sorted(POLICY_ABS_TOL))
def test_cand_engines_policy_conformance(corpus, policy):
    """The ULP conformance contract holds PER POLICY: under a reduced-
    precision policy the fused kernels still match the reference engine
    at float32 ulp distance (both paths consume the identical reduced
    handoffs — the policy moves both, not their difference), while the
    policy itself drifts from float32 only within its measured band."""
    nq, b = 4, 12
    qi, qw = corpus.ids[:nq], corpus.w[:nq]
    rng = np.random.default_rng(zlib.crc32(policy.encode()))
    cand = _random_cand(rng, corpus.n, nq, b)
    tol = POLICY_ABS_TOL[policy]
    for method in CAND_METHODS:
        f32_s = retrieval.cand_scores(corpus, qi, qw, cand, method=method,
                                      iters=2)
        ref_s = retrieval.cand_scores(corpus, qi, qw, cand, method=method,
                                      iters=2, precision=policy)
        ker_s = retrieval.cand_scores(corpus, qi, qw, cand, method=method,
                                      iters=2, precision=policy,
                                      use_kernels=True, block_n=8,
                                      block_v=64)
        assert_ulp_equal(ker_s, ref_s, err_msg=f"{policy}:{method}")
        np.testing.assert_allclose(np.asarray(ref_s), np.asarray(f32_s),
                                   atol=tol, rtol=0,
                                   err_msg=f"{policy}:{method} vs f32")
        # the drift must be real: a bitwise-f32 "bf16" run means the
        # policy kwarg fell off the stack (see analysis.precision_lint)
        assert float(np.abs(np.asarray(ref_s, np.float64)
                            - np.asarray(f32_s, np.float64)).max()) > 0.0, \
            f"{policy}:{method} scored bitwise f32 — policy ignored"


def test_ict_engine_all_remainder_query_finite(corpus):
    """Same contract through the full engine: an unnormalized query whose
    capacities absorb only a quarter of each row's mass stays finite and
    ulp-identical across the kernel and reference paths."""
    nq, b = 2, 6
    qi = corpus.ids[:nq]
    qw = corpus.w[:nq] * 0.25                 # total capacity 0.25 per query
    cand = _random_cand(np.random.default_rng(0), corpus.n, nq, b)
    ref_s = np.asarray(lc.lc_ict_scores_cand(corpus, qi, qw, cand))
    ker_s = np.asarray(lc.lc_ict_scores_cand(corpus, qi, qw, cand,
                                             use_kernels=True, block_n=8,
                                             block_v=32))
    assert_ulp_equal(ker_s, ref_s, err_msg="ict all-remainder")
    assert float(np.abs(ker_s).max()) < 1e6, \
        "all-remainder ICT scores exploded: PAD_DIST dump regression"
