"""Fused candidate kernel (``kernels/cand_pour``): share of its device
time that the least possible time of its work (``work/cand_pallas.py``)
takes."""
from emd_bench.metrics_base import roofline_pct


def read(rec):
    return roofline_pct(rec, "cand_pallas")
