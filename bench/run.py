"""The benchmark's command: one run of one cell on this host's chips.

    python3 bench/run.py --workload CELL --seed N --seconds S --trace 0|1

Prints one JSON line last on standard output, and the numbers the output
check compared, each beside its limit, as the last lines of standard
error. Exits nonzero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
