"""Device time of the plain search's top-l (``emd.topl``: ``-s``,
``lax.top_k``, ``-neg``, run eagerly as programs of their own) per query
answered in the window."""
from emd_bench.layers import in_scope, ms_per_query


def read(rec):
    return ms_per_query(rec, in_scope("emd.topl"))
