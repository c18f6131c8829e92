"""Share of the window in which no operation ran on the device (serving
cell: arrivals, queueing, launches and the drain of the last answers)."""
from emd_bench.metrics_base import idle_pct


def read(rec):
    return idle_pct(rec)
