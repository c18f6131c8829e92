"""Work counts of the kernels, against arithmetic by hand: real bins
only, padded slots never."""
import numpy as np
import pytest

import emdbench_tiny as tiny

from emd_bench import harness
from emd_bench.metrics_base import call_sizes, kernel_work


def _call(engine, q_len, *, v=1000, m=10, n=50, row_nnz=400.0, top_l=4):
    return dict(v=v, m=m, n=n, q_len=np.asarray(q_len), row_nnz=row_nnz,
                engine=engine, top_l=top_l)


ACT = {"method": "act", "iters": 3, "top_l": 4}
FAST = {"method": "act", "iters": 7, "top_l": 4, "cascade": "fast",
        "stages": [["wcd", 0.4], ["rwmd", 0.1]], "rescorer": ["act", 3]}


def test_dist_topk_by_hand():
    f, b = kernel_work("dist_topk").per_call(_call(ACT, [5, 7]))
    assert f == 2 * 1000 * 10 * 12
    assert b == 4 * 1000 * 10 + 4 * 11 * 12 + 8 * 1000 * 4 * 2


def test_act_phase2_by_hand():
    f, b = kernel_work("act_phase2").per_call(_call(ACT, [5, 7]))
    assert f == 2 * 4 * 2 * 400
    assert b == 8 * 400 + 2 * (4 * 1000 * 7 + 4 * 50)


def test_cand_pallas_by_hand():
    # n=50: wcd keeps 20, rwmd keeps 5; rwmd scores 20 rows (k=1),
    # act-3 rescores 5 (k=4); 8 real bins per row on average.
    f, b = kernel_work("cand_pallas").per_call(_call(FAST, [5, 7]))
    assert f == 2 * (20 * 8 * 2 * 1 + 5 * 8 * 2 * 4)
    assert b == 2 * ((20 * (8 * 8 + 4) + 4 * 1000 * 1)
                     + (5 * (8 * 8 + 4) + 4 * 1000 * 7))


def test_kernels_off_the_path_count_nothing():
    assert kernel_work("dist_topk").per_call(_call(FAST, [5])) is None
    assert kernel_work("act_phase2").per_call(_call(FAST, [5])) is None
    assert kernel_work("cand_pallas").per_call(_call(ACT, [5])) is None


@pytest.mark.parametrize("kernel", ["dist_topk", "act_phase2"])
def test_padding_does_not_raise_the_counts(kernel):
    """The same documents and queries with twice the padded slots: the
    same work."""
    import dataclasses

    import jax.numpy as jnp

    from emd_bench.gen import text

    cell = tiny.tiny_cell("news-act7-batch")
    run = harness.Run(cell, 1, 1.0, False, 0.0)
    data = text.make(cell.config, 11, 8)
    wide = dataclasses.replace(
        data, **{k: jnp.pad(getattr(data, k), ((0, 0), (0, 64)))
                 for k in ("ids", "w", "q_ids", "q_w")})
    counts = []
    for d in (data, wide):
        run.data = d
        rec = type("R", (), dict(run=run))
        counts.append(kernel_work(kernel).per_call(
            call_sizes(rec, range(8))))
    assert counts[0] == counts[1]


def test_peak_table_knows_v5e_and_refuses_the_rest():
    p = harness.peak_table("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    with pytest.raises(harness.Refused):
        harness.peak_table("TPU v9 imaginary")
