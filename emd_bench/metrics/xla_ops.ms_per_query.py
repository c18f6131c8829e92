"""Device time outside the Pallas kernels (ladder gathers, top-k, WCD,
pads, Phase 1 of candidate stages) per query answered in the window (batch
cells)."""
from emd_bench.metrics_base import xla_ms_per_query


def read(rec):
    return xla_ms_per_query(rec)
