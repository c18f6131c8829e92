"""Device time whose innermost scope is ``emd.ladder_gather`` (the
per-slot ladder gathers of Phase 2 and what XLA makes of them: relayout
copies, the loops that move their result) per query answered in the
window."""
from emd_bench.layers import in_scope, ms_per_query


def read(rec):
    return ms_per_query(rec, in_scope("emd.ladder_gather"))
