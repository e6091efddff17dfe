"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic, limits and metrics are found by
name from BENCHMARK.json at the root of the checkout (see
bench/harness.py).  The last line on stdout is the result as one JSON
object; the compared numbers, each beside its limit, are the last lines
on stderr.  Without a TPU, or with fewer chips than the cell asks for,
the run exits with code 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main(t_start=T_START))
