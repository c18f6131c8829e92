"""Device time under the cascade's stage 1 (``emd.cascade.stage1.<method>``:
its scoring, with Phase 1 and the pour inside it, its selection and row
order) per query answered in the window."""
from emd_bench.layers import ms_per_query, under


def read(rec):
    return ms_per_query(rec, under("emd.cascade.stage1."))
