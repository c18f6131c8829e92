"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("v,h,m,k", [
    (64, 32, 8, 1), (100, 50, 16, 4), (256, 256, 4, 8), (70, 33, 3, 2),
    (512, 17, 300, 8), (31, 128, 2, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dist_topk_matches_ref(v, h, m, k, dtype, rng):
    coords = jnp.asarray(rng.normal(size=(v, m)), dtype)
    qc = jnp.asarray(rng.normal(size=(h, m)), dtype)
    qmask = jnp.asarray(rng.uniform(size=h) > 0.2, jnp.float32)
    if not float(qmask.sum()):
        qmask = qmask.at[0].set(1.0)
    z, s = ops.dist_topk(coords, qc, k, qmask=qmask, block_v=32, block_h=16)
    zr, sr = ref.dist_topk_ref(coords, qc, qmask, k)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(z), np.asarray(zr), rtol=tol,
                               atol=tol)
    # indices may differ only under distance ties
    mismatch = np.asarray(s) != np.asarray(sr)
    if mismatch.any():
        zv = np.asarray(z)
        assert np.allclose(zv[mismatch], np.asarray(zr)[mismatch], atol=tol)


@pytest.mark.parametrize("n,hmax,iters", [
    (10, 7, 1), (64, 32, 3), (33, 17, 7), (128, 500, 2), (5, 9, 15),
])
def test_act_phase2_matches_ref(n, hmax, iters, rng):
    x = jnp.asarray(rng.uniform(size=(n, hmax)) *
                    (rng.uniform(size=(n, hmax)) > 0.3), jnp.float32)
    zg = jnp.asarray(np.sort(rng.uniform(size=(n, hmax, iters + 1)), axis=-1),
                     jnp.float32)
    wg = jnp.asarray(rng.uniform(size=(n, hmax, iters)) * 0.3, jnp.float32)
    # the kernel takes rung-major ladders; the oracle rung-last ones
    t = ops.act_phase2(x, jnp.moveaxis(zg, -1, 0), jnp.moveaxis(wg, -1, 0),
                       block_n=16, block_h=8)
    tr = ref.act_phase2_ref(x, zg, wg)
    np.testing.assert_allclose(np.asarray(t), np.asarray(tr)[:, 0],
                               rtol=1e-5, atol=1e-6)


def test_act_phase2_conserves_mass_cost_bound(rng):
    """Poured cost is bounded by total mass x max cost (sanity invariant)."""
    n, hmax, it = 32, 16, 3
    x = jnp.asarray(rng.uniform(size=(n, hmax)), jnp.float32)
    zg = jnp.asarray(np.sort(rng.uniform(size=(n, hmax, it + 1)), axis=-1),
                     jnp.float32)
    wg = jnp.asarray(rng.uniform(size=(n, hmax, it)), jnp.float32)
    t = ops.act_phase2(x, jnp.moveaxis(zg, -1, 0), jnp.moveaxis(wg, -1, 0))
    bound = np.asarray(jnp.sum(x, axis=1)) * float(zg.max())
    assert (np.asarray(t) <= bound + 1e-5).all()
    assert (np.asarray(t) >= 0).all()


@pytest.mark.parametrize("nq,v,h,m,k", [
    (1, 64, 32, 8, 4), (3, 100, 50, 16, 4), (5, 70, 33, 3, 2),
])
def test_dist_topk_batched_matches_ref(nq, v, h, m, k, rng):
    coords = jnp.asarray(rng.normal(size=(v, m)), jnp.float32)
    qcs = jnp.asarray(rng.normal(size=(nq, h, m)), jnp.float32)
    qmask = jnp.asarray(rng.uniform(size=(nq, h)) > 0.2, jnp.float32)
    qmask = qmask.at[:, 0].set(1.0)
    z, s = ops.dist_topk_batched(coords, qcs, k, qmask=qmask, block_v=32,
                                 block_h=16)
    zr, sr = ref.dist_topk_batched_ref(coords, qcs, qmask, k)
    assert z.shape == (nq, v, k)
    np.testing.assert_allclose(np.asarray(z), np.asarray(zr), rtol=1e-5,
                               atol=1e-5)
    mismatch = np.asarray(s) != np.asarray(sr)
    if mismatch.any():                       # ties may reorder indices
        assert np.allclose(np.asarray(z)[mismatch],
                           np.asarray(zr)[mismatch], atol=1e-5)


@pytest.mark.parametrize("nq,n,hmax,iters", [
    (1, 10, 7, 1), (4, 33, 17, 3), (6, 16, 9, 7),
])
def test_act_phase2_batched_matches_ref(nq, n, hmax, iters, rng):
    x = jnp.asarray(rng.uniform(size=(n, hmax)) *
                    (rng.uniform(size=(n, hmax)) > 0.3), jnp.float32)
    zg = jnp.asarray(np.sort(rng.uniform(size=(nq, n, hmax, iters + 1)), -1),
                     jnp.float32)
    wg = jnp.asarray(rng.uniform(size=(nq, n, hmax, iters)) * 0.3,
                     jnp.float32)
    t = ops.act_phase2_batched(x, jnp.moveaxis(zg, -1, 1),
                               jnp.moveaxis(wg, -1, 1), block_n=16, block_h=8)
    tr = ref.act_phase2_batched_ref(x, zg, wg)
    assert t.shape == (nq, n)
    np.testing.assert_allclose(np.asarray(t), np.asarray(tr), rtol=1e-5,
                               atol=1e-6)


def test_dist_topk_sorted_ascending(rng):
    coords = jnp.asarray(rng.normal(size=(64, 5)), jnp.float32)
    qc = jnp.asarray(rng.normal(size=(40, 5)), jnp.float32)
    z, _ = ops.dist_topk(coords, qc, 6, block_v=32, block_h=16)
    zv = np.asarray(z)
    assert (np.diff(zv, axis=1) >= -1e-6).all()


def test_kernel_path_in_engine(rng):
    from repro.core.lc import lc_act_scores
    from repro.data.synth import make_text_like
    corpus, _ = make_text_like(n_docs=10, vocab=64, m=8, doc_len=20, hmax=12)
    for iters in (0, 2):
        a = lc_act_scores(corpus, corpus.ids[0], corpus.w[0], iters=iters)
        b = lc_act_scores(corpus, corpus.ids[0], corpus.w[0], iters=iters,
                          use_kernels=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
