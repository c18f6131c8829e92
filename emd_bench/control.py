"""Readings of the compared numbers for the program and its controls.

    python3 emd_bench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 5

For each seed, in one process: the cell's data and a short window of its
own traffic, then the compared numbers of the program's answers (its
lower reading) and of two controls, the reference put in the program's
place at a precision below the configuration's: ``high`` (float32
products in three bfloat16 passes) and ``bf16`` (the Phase-1 ladders
rounded to bfloat16). One JSON line per seed. The benchmark's own runs
never run this.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from emd_bench import harness  # noqa: E402

CONTROLS = {"high": dict(passes=3), "bf16": dict(storage="bfloat16")}


def readings(cell, seed: int, seconds: float) -> dict:
    """The numbers of the program and of each control on one seed."""
    run = harness.Run(cell, seed, seconds, False, time.monotonic())
    run.data = harness.make_data(cell, seed)
    win = harness.load_loop(cell).run(run)
    run.index = None
    gc.collect()
    sample = cell.traffic["check_sample"]
    out = {"seed": seed, "answers": len(win.answers),
           "program": harness.check_answers(run, win, sample)}
    for name, kw in CONTROLS.items():
        out[name] = harness.check_answers(run, win, sample, **kw)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell, _ = harness.boot(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
