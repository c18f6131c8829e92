"""The segmented row layout of the kernel pour's ladder gather.

``EmdIndex.build`` on ``backend="pallas"`` cuts each row's real bins into
lane-wide segments (``lc.segment_rows``), and the batched ACT kernel pour
gathers ladders for those segments only. Its scores must equal the
reference backend's, which pours over the padded (n, hmax) rows, to
float32 rounding, whatever the rows hold: no real bin, every slot real,
zero-weight slots among the real bins, rows wider than one segment.
"""
import numpy as np
import pytest

from repro.api import EmdIndex, EngineConfig
from repro.core import lc


def _corpus(hmax: int, *, interleave: bool, n: int = 20, v: int = 64,
            m: int = 4, seed: int = 0) -> lc.Corpus:
    """Rows of mixed length: row 0 has no real bin, row 1 fills every
    slot, the rest hold 1..hmax real bins. With ``interleave`` the real
    bins sit at random slots among the zero-weight ones, else in a
    prefix."""
    rng = np.random.default_rng(seed)
    lengths = np.concatenate([[0, hmax], rng.integers(1, hmax + 1, n - 2)])
    ids = np.zeros((n, hmax), np.int32)
    w = np.zeros((n, hmax), np.float32)
    for r, length in enumerate(lengths):
        slots = (np.sort(rng.choice(hmax, length, replace=False))
                 if interleave else np.arange(length))
        ids[r, slots] = rng.integers(0, v, length)
        x = rng.exponential(size=length).astype(np.float32)
        w[r, slots] = x / max(x.sum(), 1e-30)
    coords = rng.normal(size=(v, m)).astype(np.float32)
    return lc.Corpus(ids=ids, w=w, coords=coords)


def _queries(nq: int, v: int, h: int = 12, seed: int = 1):
    rng = np.random.default_rng(seed)
    ids = np.stack([rng.choice(v, h, replace=False) for _ in range(nq)])
    w = rng.exponential(size=(nq, h)).astype(np.float32)
    return ids.astype(np.int32), w / w.sum(axis=1, keepdims=True)


@pytest.mark.parametrize("hmax,interleave,nq,block_q", [
    (300, True, 5, 2),      # three segments at most, the last one partial
    (300, False, 4, 4),     # real bins already a prefix, nq == block_q
    (200, True, 3, 8),      # nq below one query block
    (128, True, 7, 4),      # one whole-lane segment per row
    (20, True, 5, 2),       # rows narrower than a lane: one segment of 24
    (20, False, 3, 1),
], ids=["h300-interleaved", "h300-prefix", "h200-interleaved",
        "h128-interleaved", "h20-interleaved", "h20-prefix"])
def test_segmented_pour_matches_reference(hmax, interleave, nq, block_q):
    corpus = _corpus(hmax, interleave=interleave)
    q_ids, q_w = _queries(nq, corpus.v)
    cfg = EngineConfig(method="act", iters=3, backend="pallas",
                       block_q=block_q, block_n=16, block_v=32, top_l=4)
    pal = EmdIndex.build(corpus, cfg)
    assert pal.gather_fill_pct is not None
    ref = EmdIndex.build(corpus, EngineConfig(method="act", iters=3,
                                              block_q=block_q, top_l=4))
    assert ref.gather_fill_pct is None
    got = np.asarray(pal.scores(q_ids, q_w))
    want = np.asarray(ref.scores(q_ids, q_w))
    assert got.shape == (nq, corpus.n)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert (got[:, 0] == 0.0).all()        # the row with no real bin
    _, i_pal = pal.search(q_ids, q_w)
    _, i_ref = ref.search(q_ids, q_w)
    np.testing.assert_array_equal(np.asarray(i_pal), np.asarray(i_ref))


def _hand_corpus(hmax: int, real_slots: list[list[int]]) -> lc.Corpus:
    ids = np.zeros((len(real_slots), hmax), np.int32)
    w = np.zeros((len(real_slots), hmax), np.float32)
    for r, slots in enumerate(real_slots):
        ids[r, slots] = np.asarray(slots, np.int32) + 1
        w[r, slots] = 1.0 / max(len(slots), 1)
    return lc.Corpus(ids=ids, w=w, coords=np.zeros((hmax + 1, 2), np.float32))


@pytest.mark.parametrize("hmax,real_slots,block_n,width,S,rows,fill", [
    # width 128: an empty row, a full row (3 segments), 5 bins spread
    # over the row (1), 129 bins (2); 6 segments padded to a block of 8
    (300, [[], list(range(300)), [0, 2, 4, 100, 299], list(range(129))],
     8, 128, 8, [[8, 8, 8], [0, 1, 2], [3, 8, 8], [4, 5, 8]],
     100.0 * 434 / (8 * 128)),
    # rows narrower than a lane: width round_up(20, 8) = 24, one segment
    # per non-empty row; 3 segments in a block of min(256, 8)
    (20, [[1, 19], [], list(range(20)), [7]], 256, 24, 8,
     [[0], [8], [1], [2]], 100.0 * 23 / (8 * 24)),
], ids=["h300", "h20"])
def test_segment_rows_layout(hmax, real_slots, block_n, width, S, rows,
                             fill):
    """S, each row's segments, their contents and the fill of a
    hand-made corpus."""
    corpus = _hand_corpus(hmax, real_slots)
    seg, got_fill = lc.segment_rows(corpus, block_n)
    assert seg.width == width
    assert seg.ids.shape == seg.w.shape == (S, width)
    np.testing.assert_array_equal(np.asarray(seg.rows), rows)
    assert got_fill == pytest.approx(fill)
    ids, w = np.asarray(seg.ids), np.asarray(seg.w)
    for r, slots in enumerate(real_slots):
        mine = [s for s in rows[r] if s < S]
        # the row's real bins, in their order, then zero-weight slots
        got_ids = ids[mine].reshape(-1)
        got_w = w[mine].reshape(-1)
        np.testing.assert_array_equal(got_ids[:len(slots)],
                                      np.asarray(slots) + 1)
        assert (got_w[:len(slots)] > 0).all()
        assert (got_w[len(slots):] == 0).all()
        assert (got_ids[len(slots):] == 0).all()
    used = {s for row in rows for s in row if s < S}
    assert (w[[s for s in range(S) if s not in used]] == 0).all()


def test_fill_pct_reader_reads_the_built_index():
    """The benchmark's ``ladder_gather.fill_pct`` reader returns the
    index's fill, and nothing for an index without the segmented layout
    or a program that has no such attribute."""
    import importlib.util
    import types
    from pathlib import Path

    path = (Path(__file__).resolve().parents[1] / "emd_bench" / "metrics"
            / "ladder_gather.fill_pct.py")
    spec = importlib.util.spec_from_file_location("fill_pct_reader", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    def rec(index):
        return types.SimpleNamespace(run=types.SimpleNamespace(index=index))

    corpus = _corpus(300, interleave=False)
    pal = EmdIndex.build(corpus, EngineConfig(method="act", iters=3,
                                              backend="pallas", block_n=16))
    _, fill = lc.segment_rows(corpus, 16)
    assert reader.read(rec(pal)) == fill == pal.gather_fill_pct
    assert 0.0 < fill <= 100.0
    assert reader.read(rec(EmdIndex.build(corpus))) is None
    assert reader.read(rec(object())) is None
