"""Run one benchmark cell on the chips of this machine.

  python3 perfbench/run.py --workload covtype.ca32 --seed 7 --seconds 30 \
      --trace 0

Prints progress, then the compared numbers with their limits, on standard
error, and one JSON result line as the last line of standard output. Exits
nonzero, printing no result, when JAX finds no TPU or fewer chips than the
cell needs.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[0] = str(Path(__file__).resolve().parents[1])

from perfbench import harness  # noqa: E402

if __name__ == "__main__":
    harness.main(t_start=T0)
