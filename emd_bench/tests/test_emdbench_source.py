"""A cascade fed by a candidate source that its mix names, on the CPU at
tiny sizes: the harness builds the source through the program's
registry, judges the answers by recall against the rescorer's exact top
l over the whole corpus, and fails answers that miss it or misstate a
score."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import emdbench_tiny as tiny

from emd_bench import check, harness

#: Probes 6 of 8 k-means cells, then the 160 centroid-nearest probed rows.
SOURCE = {"kind": "centroid_lsh", "quantizer": "kmeans", "n_buckets": 8,
          "probes": 6, "bucket_cap": 128, "refine": 160}
#: At n=300 a 5% RWMD budget keeps top_l rows alone; these budgets leave
#: the rescorer something to rank.
STAGES = [["wcd", 0.6], ["rwmd", 0.25]]
LIMITS = {"score_err": 1e-4, "recall_miss": 0.4, "bad_answers": 0,
          "lost": 0}


def sourced_cell(source=SOURCE, limits=LIMITS, **traffic):
    cell = tiny.tiny_cell("news-fast-cascade", check_sample=32, **traffic)
    engine = dict(cell.traffic["engine"], stages=STAGES, source=source)
    return dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                  engine=engine),
                               limits=dict(limits))


def test_harness_builds_the_source_the_mix_names():
    cell = sourced_cell()
    run = harness.Run(cell, 3, 1.0, False, 0.0)
    run.data = harness.make_data(cell, 3)
    index = run.build_index()
    spec = index.config.cascade_spec
    assert spec.source.describe() == "centroid_lsh[kmeans b8 p6 cap128 r160]"
    assert spec.sourced and index.source is not None
    assert [(s.method, s.budget) for s in spec.stages] == \
        [tuple(s) for s in STAGES]


def test_mix_without_source_builds_todays_spec():
    cell = tiny.tiny_cell("news-fast-cascade")
    run = harness.Run(cell, 3, 1.0, False, 0.0)
    run.data = harness.make_data(cell, 3)
    spec = run.build_index().config.cascade_spec
    assert spec.source is None and not spec.sourced
    assert harness.compared(cell) == check.NUMBERS


@pytest.mark.parametrize("source,match", [
    ({"kind": "ivf_pq"}, "registered: .*centroid_lsh"),
    (dict(SOURCE, probe=3), "has no field"),
    (dict(SOURCE, probes=9), "exceeds"),
], ids=["kind", "field", "value"])
def test_unknown_sources_are_refused(source, match):
    with pytest.raises(harness.Refused, match=match):
        harness.source_spec(source)


def test_sound_sourced_run_is_correct():
    out = tiny.run_tiny(sourced_cell())
    assert out["correct"], out["checks"]
    assert tuple(out["checks"]) == check.SOURCED_NUMBERS
    assert out["checks"]["recall_miss"]["value"] < 0.25


def _swap_half_for_far_rows(search):
    """Each answer keeps its best half; the other half is the corpus's
    worst rows by the rescorer (LC-ACT-3), with their true scores."""
    from repro.core import retrieval

    def broken(self, q_ids, q_w, *a, **k):
        s, r = search(self, q_ids, q_w, *a, **k)
        nq, l = r.shape
        every = jnp.broadcast_to(jnp.arange(self.n, dtype=jnp.int32),
                                 (nq, self.n))
        full = retrieval.cand_scores(self.corpus, q_ids, q_w, every,
                                     method="act", iters=3)
        far_s, far_r = jax.lax.top_k(full, l - l // 2)
        return (jnp.concatenate([s[:, :l // 2], far_s], axis=1),
                jnp.concatenate([r[:, :l // 2], far_r], axis=1))
    return broken


def _alter_one_score(search):
    def broken(self, q_ids, q_w, *a, **k):
        s, r = search(self, q_ids, q_w, *a, **k)
        return s.at[:, 0].add(0.01 * s[:, -1]), r
    return broken


@pytest.mark.parametrize("fault,number", [
    (_swap_half_for_far_rows, "recall_miss"),
    (_alter_one_score, "score_err"),
], ids=["swap-half", "alter-score"])
def test_broken_sourced_program_is_not_correct(monkeypatch, fault, number):
    from repro.api import EmdIndex

    monkeypatch.setattr(EmdIndex, "search", fault(EmdIndex.search))
    out = tiny.run_tiny(sourced_cell())
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"], out["checks"]


@pytest.mark.parametrize("cell", [
    sourced_cell(limits={k: v for k, v in LIMITS.items()
                         if k != "recall_miss"}),
    dataclasses.replace(tiny.tiny_cell("news-act7-batch"), traffic=dict(
        tiny.tiny_cell("news-act7-batch").traffic,
        engine={"source": SOURCE})),
], ids=["no-recall-limit", "no-stages"])
def test_sourced_cells_that_cannot_be_judged_are_refused(cell):
    with pytest.raises(harness.Refused):
        tiny.run_tiny(cell)


def test_recall_counts_rows_tied_with_the_lth():
    ref = np.array([0.0, 1.0, 2.0, 2.0, 3.0, 9.0])
    top = np.array([0.0, 1.0, 2.0])                 # l = 3
    tied = check.judge_answer([0.0, 1.0, 2.0], [0, 1, 3], ref, top, 6)
    assert tied["recall"] == 1.0 and tied["topl_gap"] == 0.0
    worse = check.judge_answer([0.0, 1.0, 3.0], [0, 1, 4], ref, top, 6,
                               tie=0.4)
    assert worse["recall"] == pytest.approx(2 / 3)
    near = check.judge_answer([0.0, 1.0, 3.0], [0, 1, 4], ref, top, 6,
                              tie=0.5)
    assert near["recall"] == 1.0
    out = check.combine([tied, worse], lost=0)
    assert out["recall_miss"] == pytest.approx(1 / 6)
    bad = check.judge_answer([0.0, 1.0, 1.0], [0, 1, 1], ref, top, 6)
    assert bad["bad_answers"] == 1 and bad["recall"] == 0.0
