"""On-chip benchmark of the Graph4Rec recommender.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the cell's chips. The
last line on standard output is the result object; the correctness
numbers and their limits are the last lines on standard error. See
``harness.py`` for how a workload's files are found.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

if __name__ == "__main__":
    harness.run(sys.argv[1:], T_START, HERE.parents[1])
