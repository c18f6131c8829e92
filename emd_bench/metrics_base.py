"""What a per-layer metric reader gets, and the arithmetic they share.

A reader is ``metrics/<metric>.py`` with ``read(record) -> float | None``;
``None`` means it found nothing to read, and the metric is left out.
"""
from __future__ import annotations

import dataclasses

from emd_bench.harness import BENCH, load_module


@dataclasses.dataclass
class Record:
    run: object            # harness.Run: cell, config, traffic, data
    win: object            # harness.Window: counters, calls
    trace: object          # trace.Reduced of the window, or None
    peaks: dict            # peaks.json entry of the device kind


def kernel_work(kernel: str):
    """The ``work/<kernel>.py`` module."""
    return load_module(BENCH / "work" / f"{kernel}.py")


def call_sizes(rec: Record, queries) -> dict:
    """Sizes of one search call over pool ``queries``, for the work
    counts: real bins of each query and of the corpus rows, counted from
    the weights (a padding slot weighs 0) once per run."""
    import numpy as np

    d = rec.run.data
    if getattr(rec.run, "real_bins", (None,))[0] is not d:
        rec.run.real_bins = (d, np.count_nonzero(np.asarray(d.q_w), axis=1),
                             float(np.count_nonzero(np.asarray(d.w))))
    _, q_len, row_nnz = rec.run.real_bins
    return dict(v=d.coords.shape[0], m=d.coords.shape[1], n=d.w.shape[0],
                q_len=q_len[np.asarray(list(queries))], row_nnz=row_nnz,
                engine=rec.run.engine(), top_l=rec.run.engine()["top_l"])


def roofline_pct(rec: Record, kernel: str) -> float | None:
    """Share of the kernel's device time that its least possible time
    (the larger of operations over peak FLOP/s and compulsory bytes over
    peak bandwidth, per call) takes, in percent."""
    if rec.trace is None:
        return None
    work = kernel_work(kernel)
    seconds = rec.trace.time_of(work.TRACE_NAME)
    if seconds <= 0:
        return None
    least = 0.0
    for queries in rec.win.calls:
        w = work.per_call(call_sizes(rec, queries))
        if w is None:
            return None
        flops, nbytes = w
        least += max(flops / rec.peaks["flops_per_s"],
                     nbytes / rec.peaks["bytes_per_s"])
    return 100.0 * least / seconds


def idle_pct(rec: Record) -> float | None:
    """Share of the window in which no operation ran on the device."""
    if rec.trace is None or rec.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s / rec.trace.window_s)


def xla_ms_per_query(rec: Record) -> float | None:
    """Device time outside the kernels (every operation but the custom
    calls to ``tpu_custom_call``) per query answered in the window."""
    if rec.trace is None or not rec.win.counters.get("queries"):
        return None
    return 1e3 * (rec.trace.busy_s - rec.trace.kernel_s) \
        / rec.win.counters["queries"]
