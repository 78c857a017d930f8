"""Benchmark entry point: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
numbers compared for ``correct`` end standard error.  Exits non-zero with
no result when JAX finds no TPU, or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import harness  # noqa: E402  (the clock starts before any import)

if __name__ == "__main__":
    harness.main(t_start=T_START)
