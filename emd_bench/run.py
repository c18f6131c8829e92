"""Run one cell of the EMD search benchmark on the TPU of this machine.

    python3 emd_bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Prints progress and each compared number beside its limit on standard
error, and one JSON object as the last line of standard output. Exits
non-zero, printing no result, without a TPU or without the program's
sources in the checkout.
"""
import time

T_START = time.monotonic()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from emd_bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t_start=T_START))
