"""Device time whose innermost scope is ``emd.phase2`` (Phase 2/3 less
the ladder gather: the query-block loop, its pads and slices, the
``act_phase2`` kernel) per query answered in the window."""
from emd_bench.layers import in_scope, ms_per_query


def read(rec):
    return ms_per_query(rec, in_scope("emd.phase2"))
