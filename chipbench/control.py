"""Readings behind the limits of ``correct``: the program's and the
control's, on many seeds, in one process.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--rehearse]

Set-up runs once; then, per seed, one window of the cell and the check
twice over the same sample of answers: as the benchmark makes it, and
with the control (the reference computed in bfloat16) put in the
program's place.  One JSON line per seed.  The benchmark's own runs
never run the control.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def readings(workload, seeds, seconds, rehearse=False, bm=None, out=None):
    import json

    from bench import check, device, runner
    from bench.spec import Benchmark
    bm = bm or Benchmark()
    cell, gen, ctx = runner.prepare(bm, workload, seeds[0], rehearse)
    device.check(cell["chips"], rehearse)
    system = gen.setup(ctx)
    lines = []
    for seed in seeds:
        ctx.seed = int(seed)
        res = gen.window(system, ctx, seconds)
        program = runner.compare(bm, ctx, res)
        control = runner.compare(bm, ctx, res, control=True)
        line = {"seed": seed, "answers": len(res["answers"]),
                "program": {k: v["value"] for k, v in program.items()},
                "program_correct": check.passed(program),
                "control": {k: v["value"] for k, v in control.items()},
                "control_correct": check.passed(control)}
        lines.append(line)
        print(json.dumps(line), file=out or sys.stdout, flush=True)
    return lines


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one window each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", help="checkout whose BENCHMARK.json to read "
                                   "(default: this one)")
    args = ap.parse_args(argv)
    from bench.spec import Benchmark
    bm = Benchmark(args.root) if args.root else None
    readings(args.workload, [int(s) for s in args.seeds.split(",")],
             args.seconds, args.rehearse, bm=bm)
    return 0


if __name__ == "__main__":
    sys.exit(main())
