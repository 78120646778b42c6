"""python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` once, on the TPU this process comes
up on, and prints one JSON object as the last line of its output. It
never falls back to another platform.
"""

import os
import sys
import time

_T_START = time.perf_counter()  # set-up counts from the process's start

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks.harness import main

    sys.exit(main(t_start=_T_START))
