"""Run one benchmark cell on the chips of this machine.

    python bench/run_cell.py --workload fhp2-flow.lattice --seed 7 \
        --seconds 10 --trace 0

The cell, its configuration, traffic mix, driver and metric readers are
found by name from ``BENCHMARK.json`` (see ``bench/harness.py``).  Set-up,
then a window of ``--seconds``, then the correctness check; the last line
of standard output is the result as one JSON object, and the numbers
compared for ``correct`` close standard error.  Exits non-zero, printing
no result, when JAX sees no TPU or fewer chips than the cell needs.
"""
import time

STARTED = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(started=STARTED))
