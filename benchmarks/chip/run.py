#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on this machine's chips::

    python benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Exits non-zero, printing no result, without a TPU, with fewer chips than
the cell asks for, or with the program's kernels in interpret mode.  The
last stdout line is the result; see ``harness.py``.
"""

import os
import sys
import time

T_START = time.perf_counter()
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(_ROOT, "src"), os.path.join(_ROOT, "benchmarks")]

from chip.harness import main  # noqa: E402

if __name__ == "__main__":
    main(t_start=T_START)
