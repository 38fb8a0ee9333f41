"""Chip benchmark of the vLSM store: one cell, one seed, one window.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell
asks for.  The last line of standard output is the result as one JSON
object; the numbers the correctness check compared, each beside its
limit, are the last lines of standard error.  Off a TPU it exits non-zero
and prints no result.  The cells, their files and their metrics are named
in ``BENCHMARK.json``; ``bench/lsmbench/catalog.py`` says where each part
lives.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

if __name__ == "__main__":
    from lsmbench.cli import main
    sys.exit(main(sys.argv[1:], T_START))
