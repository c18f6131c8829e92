"""Coverage for the remaining utility layers: histogram construction, the
WMD pruned-search baseline, the report builder, and the retrieval registry."""
import json

import numpy as np

from repro.core import retrieval
from repro.core.histogram import docs_to_corpus, images_to_corpus
from repro.core.wmd import wmd_search
from repro.data.synth import make_text_like


def test_docs_to_corpus_truncates_and_normalizes():
    docs = [[0, 0, 1, 2, 2, 2], [3] * 10, list(range(8))]
    coords = np.random.default_rng(0).normal(size=(8, 4))
    c = docs_to_corpus(docs, coords, hmax=4)
    w = np.asarray(c.w)
    np.testing.assert_allclose(w.sum(axis=1), 1.0, rtol=1e-6)
    # doc 2 has 8 distinct tokens but hmax=4 -> truncated to 4 bins
    assert (w[2] > 0).sum() == 4
    # doc 0: token 2 is most frequent
    ids0 = np.asarray(c.ids[0])
    assert 2 in ids0[np.asarray(w[0]) > 0]


def test_images_to_corpus_modes():
    imgs = np.zeros((3, 4, 4))
    imgs[:, 1, 1] = 1.0
    imgs[1, 2, 2] = 2.0
    sparse = images_to_corpus(imgs, include_background=False)
    dense = images_to_corpus(imgs, include_background=True)
    assert sparse.hmax == 2                      # max nonzeros
    assert dense.hmax == 16                      # every pixel
    np.testing.assert_allclose(np.asarray(dense.w).sum(1), 1.0, rtol=1e-5)
    assert sparse.coords.shape == (16, 2)


def test_wmd_search_exact_ranking_consistency():
    corpus, labels = make_text_like(n_docs=12, vocab=64, m=6, doc_len=20,
                                    hmax=12, seed=9)
    val, idx = wmd_search(corpus, 0, top_l=3)
    assert len(idx) == 3 and 0 not in idx        # self excluded
    assert (np.diff(val) >= -1e-9).all()         # sorted ascending
    # WMD distances dominate the RWMD lower bounds
    from repro.core.lc import lc_rwmd_scores
    lb = np.asarray(lc_rwmd_scores(corpus, corpus.ids[0], corpus.w[0]))
    for u, v in zip(idx, val, strict=True):
        assert v >= lb[u] - 1e-5


def test_retrieval_registry_complete():
    assert set(retrieval.METHODS) == {"rwmd", "rwmd_rev", "omr", "act",
                                      "ict", "bow", "wcd"}
    for name, spec in retrieval.METHODS.items():
        assert isinstance(spec, retrieval.MethodSpec)
        assert spec.name == name and spec.paper_name
        if spec.reverse is not None:
            assert retrieval.METHODS[spec.reverse].reverse == name


def test_report_builder(tmp_path):
    from repro.analysis import report
    rec = {"arch": "a", "shape": "s", "mesh": "16x16", "devices": 256,
           "t_compute": 1.0, "t_memory": 0.5, "t_collective": 2.0,
           "bottleneck": "collective", "hlo_flops": 1e15,
           "model_flops": 8e14, "useful_flops_ratio": 0.8}
    p = tmp_path / "r.jsonl"
    p.write_text(json.dumps(rec) + "\n" + json.dumps(rec) + "\n")
    recs = report.load(str(p))
    assert len(recs) == 1                        # dedup keeps last
    tbl = report.table(recs, "16x16")
    assert "| a | s |" in tbl and "0.500" in tbl
    assert "worst roofline" in report.summary(recs, "16x16")


def test_search_step_single_device_matches_engine():
    from repro.launch.search import make_search_step
    from repro.core.lc import lc_act_scores
    import jax
    corpus, _ = make_text_like(n_docs=10, vocab=64, m=6, doc_len=18,
                               hmax=10, seed=2)
    step = make_search_step(iters=2, top_l=4)
    scores, idx = jax.jit(step)(corpus.ids, corpus.w, corpus.coords,
                                corpus.ids[:3], corpus.w[:3])
    for u in range(3):
        ref = lc_act_scores(corpus, corpus.ids[u], corpus.w[u], iters=2)
        neg, ridx = jax.lax.top_k(-ref, 4)
        np.testing.assert_allclose(np.asarray(scores[u]), np.asarray(-neg),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(idx[u]), np.asarray(ridx))


def test_compile_cache_placement(tmp_path, monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    otherwise the cache goes to the fixed ``.jax_cache`` under the root."""
    import jax

    from repro.launch.compile_cache import CACHE_DIRNAME, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "op"))
        assert enable_compile_cache(tmp_path) == str(tmp_path / "op")
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(tmp_path.resolve() / CACHE_DIRNAME)
        assert enable_compile_cache(tmp_path) == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
