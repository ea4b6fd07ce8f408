"""Run one benchmark cell once and print its result as one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  The run needs a TPU: with
no accelerator, or fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""

import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
