"""Device time whose innermost scope is ``emd.phase1`` (distances, the
per-row top-k and ``take_bins``, the ``dist_topk`` kernel among them) per
query answered in the window."""
from emd_bench.layers import in_scope, ms_per_query


def read(rec):
    return ms_per_query(rec, in_scope("emd.phase1"))
