"""Benchmark entry: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell asks
for; without a TPU it exits non-zero and prints no result.  See
``bench/lib/harness.py``.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, "lib"))
sys.path.insert(0, os.path.join(os.path.dirname(_HERE), "src"))

if __name__ == "__main__":
    import harness

    sys.exit(harness.main(t_start=T_START))
