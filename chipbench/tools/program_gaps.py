"""Run one cell once, as ``chipbench/run.py`` does, and also charge the
traced window's idle gaps to the program's own ``fifo.`` spans:

    python3 chipbench/tools/program_gaps.py --workload <cell> --seed <n> \
        --seconds <s> --trace 1

Standard output is the harness's, unchanged.  After the run, standard
error ends with one JSON line, ``{"program_idle_gaps": [[span,
seconds], ...]}`` (``bench.program.program_idle_gaps``; empty without
``--trace 1``).
"""

import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main() -> int:
    from bench import program, runner, trace
    found = []
    reduce = trace.reduce

    def reduce_and_charge(path, n_devices, *args, **kwargs):
        red = reduce(path, n_devices, *args, **kwargs)
        found.extend(program.program_idle_gaps(path, n_devices))
        return red
    trace.reduce = reduce_and_charge
    rc = runner.main(t_start=T_START)
    print(json.dumps({"program_idle_gaps": found}), file=sys.stderr,
          flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
