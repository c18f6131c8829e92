"""Phase-1 kernel (``kernels/dist_topk``): share of its device time that
the least possible time of its work (``work/dist_topk.py``) takes."""
from emd_bench.metrics_base import roofline_pct


def read(rec):
    return roofline_pct(rec, "dist_topk")
