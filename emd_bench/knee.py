"""Sweep of offered rates for a serving cell, to find its knee.

    python3 emd_bench/knee.py --workload <cell> --seeds 1,2 \
        --rates 10,20,30 --seconds 50

For each seed and rate, in one process: the cell's own loop at that rate
(the mix's other parameters as they are), then one JSON line with the
requests offered and answered, the latency p50 and p95 from the due
time, and the loop's counters. Last, two knees: ``knee_qps``, the
highest rate at which the server keeps pace on every seed (its backlog
does not grow: ``kept_pace_pct``, the answers of the window's second
half over the requests due in it, at least 98), and ``knee_p95_qps``,
the highest that also keeps the seeds' median p95 under four times its
value at the lowest rate swept. The benchmark's own runs never run this.
"""
import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from emd_bench import harness  # noqa: E402

#: ``kept_pace_pct``, and p95 over the lowest rate's p95, that a rate at
#: or below the knee keeps.
KEEPS_PACE_PCT, P95_GROWTH = 98.0, 4.0


def reading(cell, seed: int, seconds: float, rate: float, data) -> dict:
    """One window of the cell's loop at ``rate`` over ``data``."""
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                  rate_qps=rate))
    run = harness.Run(cell, seed, seconds, False, time.monotonic())
    run.data = data
    win = harness.load_loop(cell).run(run)
    return {"seed": seed, "rate_qps": rate, "offered": win.attempted,
            "answered": len(win.answers), "failed": win.failed,
            "lost": win.lost, **win.e2e, **win.counters}


def knees(rows: list[dict]) -> dict:
    """The two knees of the module docstring (None where no rate
    qualifies)."""
    by_rate = {}
    for r in rows:
        by_rate.setdefault(r["rate_qps"], []).append(r)
    pace = {rate: min(r["kept_pace_pct"] for r in rs) >= KEEPS_PACE_PCT
            for rate, rs in by_rate.items()}
    p95 = {rate: statistics.median(r["latency_p95_ms"] for r in rs)
           for rate, rs in by_rate.items()}
    base = p95[min(by_rate)]
    return {"knee_qps": max((r for r in by_rate if pace[r]), default=None),
            "knee_p95_qps": max((r for r in by_rate
                                 if pace[r] and p95[r] < P95_GROWTH * base),
                                default=None)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    args = ap.parse_args(argv)
    cell, _ = harness.boot(args.workload)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        data = harness.make_data(cell, seed)
        for rate in (float(r) for r in args.rates.split(",")):
            rows.append(reading(cell, seed, args.seconds, rate, data))
            print(json.dumps(rows[-1]), flush=True)
    print(json.dumps(knees(rows)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
