"""Device time under the cascade's stage 2 (``emd.cascade.stage2.<method>``:
its scoring, with Phase 1 and the pour inside it, its selection and row
order) per query answered in the window."""
from emd_bench.layers import ms_per_query, under


def read(rec):
    return ms_per_query(rec, under("emd.cascade.stage2."))
