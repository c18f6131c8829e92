"""The generators: fixed size multisets, statistics the configurations
state, seeds past 32 bits."""
import numpy as np
import pytest

import emdbench_tiny as tiny

from emd_bench.gen import common, image, text


def _text_cfg(**gen):
    c = tiny.harness.load_cell("news-act7-batch").config
    c = dict(c, **tiny.TEXT)
    c["generator"] = dict(c["generator"], **tiny.TEXT_GEN, **gen)
    return c


def _image_cfg():
    c = tiny.harness.load_cell("mnist-act7-batch").config
    c = dict(c, **tiny.IMAGE)
    c["generator"] = dict(c["generator"], **tiny.IMAGE_GEN)
    return c


def _rows_ok(ids, w, lens, v):
    ids, w = np.asarray(ids), np.asarray(w)
    live = w > 0
    assert (live.sum(1) == lens).all()
    assert (ids[~live] == 0).all() and (ids >= 0).all() and (ids < v).all()
    np.testing.assert_allclose(w.sum(1), 1.0, rtol=1e-5)
    for row, lv in zip(ids, live):
        assert np.unique(row[lv]).size == lv.sum()      # distinct bins


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_text_rows_are_valid_histograms(seed):
    c = _text_cfg()
    d = text.make(c, seed, 16)
    _rows_ok(d.ids, d.w, d.doc_len, c["v"])
    _rows_ok(d.q_ids, d.q_w, d.q_len, c["v"])
    np.testing.assert_allclose(np.linalg.norm(np.asarray(d.coords), axis=1),
                               1.0, rtol=1e-5)
    assert d.q_len.min() >= c["generator"]["min_query_words"]


def test_text_lengths_are_one_multiset_in_seed_order():
    c = _text_cfg()
    a, b = text.make(c, 1, 16), text.make(c, 2, 16)
    assert (np.sort(a.doc_len) == np.sort(b.doc_len)).all()
    assert (a.doc_len != b.doc_len).any()
    assert not np.array_equal(np.asarray(a.ids), np.asarray(b.ids))


def test_text_length_statistics_at_the_published_mean():
    sizes = common.lognormal_sizes(18828, 72.0, 0.9, 1, 500)
    assert 65 <= sizes.mean() <= 75
    assert sizes.min() >= 1 and sizes.max() == 500
    assert 40 <= np.median(sizes) <= 55           # heavy tail: mean > median


def test_text_common_words_are_shared():
    c = _text_cfg()
    d = text.make(c, 4, 16)
    ids, w = np.asarray(d.ids), np.asarray(d.w)
    counts = np.bincount(ids[w > 0], minlength=c["v"])
    # Zipf popularity: the most common word is in far more documents
    # than a uniform draw would put it in.
    assert counts.max() > 5 * counts[counts > 0].mean()


def test_image_rows_and_lit_pixels():
    c = _image_cfg()
    d = image.make(c, 7, 8)
    _rows_ok(d.ids, d.w, d.doc_len, c["v"])
    coords = np.asarray(d.coords)
    assert coords.shape == (784, 2) and coords.max() == 27
    g = c["generator"]
    assert g["min_lit"] <= d.doc_len.min() and d.doc_len.max() <= g["max_lit"]
    sizes = common.normal_sizes(60000, g["mean_lit"], g["sd_lit"],
                                g["min_lit"], g["max_lit"])
    assert abs(sizes.mean() - 150) < 1


def test_seed_keys_keep_64_bits():
    k1 = common.seed_key(5)
    k2 = common.seed_key(5 + 2**32)
    import jax
    assert not np.array_equal(jax.random.key_data(k1),
                              jax.random.key_data(k2))
