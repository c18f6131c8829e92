"""Phase-2/3 pour kernel (``kernels/act_phase2``): share of its device
time that the least possible time of its work (``work/act_phase2.py``)
takes."""
from emd_bench.metrics_base import roofline_pct


def read(rec):
    return roofline_pct(rec, "act_phase2")
