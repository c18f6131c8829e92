"""Device time under the cascade's rescorer (``emd.cascade.rescore.<method>``:
its scoring, with Phase 1 and the pour inside it, and the final top-l)
per query answered in the window."""
from emd_bench.layers import ms_per_query, under


def read(rec):
    return ms_per_query(rec, under("emd.cascade.rescore."))
