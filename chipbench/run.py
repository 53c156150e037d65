"""Run one benchmark cell once:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, on a machine whose JAX sees the TPU chips
the cell asks for (``BENCHMARK.json``).  ``--rehearse`` runs the cell at
its rehearsal size on the CPU instead and prints no metrics.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")

if __name__ == "__main__":
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chipbench: no program to measure: {SRC}/repro is missing",
              file=sys.stderr)
        sys.exit(2)
    sys.path[:0] = [HERE, SRC]
    from bench.runner import main
    sys.exit(main(t_start=T_START))
