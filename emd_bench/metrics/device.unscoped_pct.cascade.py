"""Share of the device's busy time in no ``emd.`` scope (cascade cells)."""
from emd_bench.layers import unscoped_pct


def read(rec):
    return unscoped_pct(rec)
