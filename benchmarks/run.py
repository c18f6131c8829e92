"""Benchmark driver — one module per paper table/figure.

  fig8_tradeoff      Fig. 8(a)  runtime vs accuracy, text (incl. WMD ref)
  sinkhorn_compare   Fig. 8(b)  ACT vs Sinkhorn, images
  table5_mnist       Table 5    sparse image precision@top-l
  table6_dense       Table 6    dense histograms (RWMD collapse)
  table3_complexity  Tables 2/3 empirical linear-scaling check
  kernels_bench      DESIGN 2   kernel traffic/fusion model
  bench_batch        serving    batched vs scanned queries/sec (+ JSON)
  bench_cascade      serving    cascaded prune-and-rescore recall/qps (+ JSON)
  bench_serve        serving    online runtime latency/tier mix vs load (+ JSON)

Each prints ``name,us_per_call,derived`` CSV rows. All retrieval-bench
entry points score through the unified ``repro.api.EmdIndex`` serving API
(``benchmarks.common.build_index``); only kernel microbenches go below it.
Run: PYTHONPATH=src python -m benchmarks.run [--only fig8]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback
from pathlib import Path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark module names")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(Path(__file__).resolve().parent.parent)
    from benchmarks import (bench_batch, bench_cascade, bench_serve,
                            fig8_tradeoff, kernels_bench, sinkhorn_compare,
                            table3_complexity, table5_mnist, table6_dense)
    mods = [table6_dense, table5_mnist, fig8_tradeoff, sinkhorn_compare,
            table3_complexity, kernels_bench, bench_batch, bench_cascade,
            bench_serve]
    print("name,us_per_call,derived")
    failures = 0
    for mod in mods:
        name = mod.__name__.split(".")[-1]
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            mod.run()
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
