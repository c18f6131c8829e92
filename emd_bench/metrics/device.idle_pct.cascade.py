"""Share of the window in which no operation ran on the device (cascade
cells)."""
from emd_bench.metrics_base import idle_pct


def read(rec):
    return idle_pct(rec)
