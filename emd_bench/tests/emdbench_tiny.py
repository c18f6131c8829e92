"""Tiny copies of the benchmark's cells for the CPU tests: the cell's own
files, with the deployment's scale cut so that a whole run takes seconds
(Pallas kernels in interpret mode)."""
from __future__ import annotations

import copy
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from emd_bench import harness  # noqa: E402

TEXT = dict(n=300, v=512, m=16, hmax=64)
TEXT_GEN = dict(chunk=64, mean_words=12.0)
IMAGE = dict(n=300)
IMAGE_GEN = dict(chunk=64)
TRAFFIC = {"batch": dict(pool=32, check_sample=8),
           "serve": dict(pool=32, rate_qps=8.0, max_batch=4)}


def bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load(name: str) -> harness.Cell:
    return harness.load_cell(name, bench())


def tiny_cell(name: str, **traffic) -> harness.Cell:
    cell = load(name)
    c = copy.deepcopy(cell.config)
    if c["generator"]["name"] == "text":
        c.update(TEXT)
        c["generator"].update(TEXT_GEN)
    else:
        c.update(IMAGE)
        c["generator"].update(IMAGE_GEN)
    t = copy.deepcopy(cell.traffic)
    t.update(TRAFFIC[t["loop"]])
    if t["loop"] == "batch":
        t["queries_per_call"] = min(t["queries_per_call"], 8)
    t.update(traffic)
    return dataclasses.replace(cell, config=c, traffic=t)


def run_tiny(cell: harness.Cell, seed: int = 3, seconds: float = 1.0):
    """A whole run of ``cell`` on the CPU, past the look for a chip."""
    import time

    import jax
    return harness.execute(cell, seed, seconds, False, time.monotonic(),
                           jax.devices())
