"""Run one benchmark cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the run's JSON result; the numbers the
correctness check compares, each beside its limit, are the last lines of
standard error.  Without an accelerator, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, os.path.join(_ROOT, "bench"))

from harness.cell import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
