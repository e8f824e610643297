"""Benchmark entry point: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout, on the machine that holds the chips
the cell asks for.  Exits non-zero, with no result, when JAX finds no
TPU or too few chips.  The last line of standard output is the result
as one JSON object; the last lines of standard error are the numbers
compared with the plain reference, each beside its limit.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
# The TPU runtime logs to a fixed path under /tmp unless told otherwise.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
