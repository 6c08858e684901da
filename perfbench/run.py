#!/usr/bin/env python3
"""Entry point: run one benchmark cell on the chip (see `harness.py`).

    python3 perfbench/run.py --workload books-pgm.probe1024 --seed 7 \\
        --seconds 10 --trace 0

Exits non-zero, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

if __name__ == "__main__":
    from perfbench.harness import main

    main(t_process=T_PROCESS)
