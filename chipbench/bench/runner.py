"""One run of one cell: set up, warm up, measure, check, report.

The last line of standard output is one JSON object::

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown",] "comparison"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from the same kind of window
recorded by the profiler.  ``comparison`` holds every number the check
compared, each beside its limit; the same lines end standard error.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Optional

from bench import check, device, trace
from bench.instrument import Tallies
from bench.spec import Benchmark, SpecError

#: the warm-up's searches and campaigns use this seed in every run, so
#: that set-up does the same work whatever ``--seed`` says
WARMUP_SEED = 2**31 - 1


@dataclasses.dataclass
class Context:
    """What a traffic generator is handed."""

    deployment: dict
    mix: dict
    seed: int
    tallies: Tallies
    warmup_seed: int = WARMUP_SEED


def _overlay(base: dict, rehearse: bool) -> dict:
    out = {k: v for k, v in base.items() if k != "rehearsal"}
    if rehearse:
        out.update(base.get("rehearsal", {}))
    return out


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def prepare(bm: Benchmark, workload: str, seed: int, rehearse: bool):
    """The cell, its generator module and the context it is handed."""
    cell = bm.cell(workload)
    mix = _overlay(bm.mix(cell["traffic"]), rehearse)
    ctx = Context(deployment=_overlay(bm.config(cell)["deployment"],
                                      rehearse),
                  mix=mix, seed=int(seed), tallies=Tallies())
    return cell, bm.generator(mix["kind"]), ctx


def compare(bm: Benchmark, ctx: Context, res: dict, control=False) -> dict:
    """Every number the check compares for the window's result ``res``."""
    designs = {name: bm.design(name) for name, _ in res["answers"]}
    comparison = check.compare(res["answers"], designs, ctx.seed,
                               control=control, bench=bm.bench,
                               **ctx.mix["check"])
    comparison.update(res.get("comparison", {}))
    return comparison


def run(workload: str, seed: int, seconds: float, traced: bool,
        rehearse: bool = False, t_start: Optional[float] = None,
        bm: Optional[Benchmark] = None, out=sys.stdout, err=sys.stderr
        ) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    bm = bm or Benchmark()
    cell, gen, ctx = prepare(bm, workload, seed, rehearse)
    dev = device.check(cell["chips"], rehearse)
    import jax
    counter = device.CompileCounter()
    system = gen.setup(ctx)
    ctx.tallies.clear()
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    setup_s = time.perf_counter() - t_start
    try:
        if traced:
            jax.profiler.start_trace(tmp, profiler_options=_profile_options())
        res = gen.window(system, ctx, seconds)
        t_end = time.perf_counter()
        if traced:
            jax.profiler.stop_trace()
        dev["memory_peak_bytes"] = device.memory_peak_bytes(cell["chips"])
        red = (trace.reduce(trace.find_xplane(tmp), cell["chips"])
               if traced else None)
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    del system
    gc.collect()

    comparison = compare(bm, ctx, res)
    correct = check.passed(comparison)

    view = SimpleNamespace(
        cell=workload, window_s=res["elapsed_s"], counters=res["counters"],
        tallies=ctx.tallies, trace=red, device_kind=dev["kind"],
        compiles_in_window=counter.between(res["t0"], t_end))
    if traced:
        metrics = {}
        for m in bm.per_layer(workload):
            value = bm.reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        dev["busy_s"], dev["window_s"] = red.busy_s, red.window_s
    else:
        values = dict(res["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in bm.end_to_end(workload)}
    result = {"correct": correct, "attempted": int(res["attempted"]),
              "failed": int(res["failed"])}
    if rehearse:
        # a CPU run reports no number under a metric's name
        result.update(rehearsal=True, computed=sorted(metrics))
    else:
        result["metrics"] = metrics
    result["device"] = dev
    if traced and not rehearse:
        result["breakdown"] = {"device_ops": [list(x) for x in red.top_ops],
                               "idle_gaps": [list(x) for x in red.idle_gaps]}
    result["comparison"] = comparison
    for name, item in comparison.items():
        bound = (f"limit {item['limit']}" if "limit" in item
                 else f"at least {item['min']}")
        print(f"check {name}: {item['value']} ({bound})", file=err)
    print(json.dumps(result), file=out, flush=True)
    return 0


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run at the cell's rehearsal size on the CPU; "
                         "prints no metrics")
    args = ap.parse_args(argv)
    try:
        return run(args.workload, args.seed, args.seconds, bool(args.trace),
                   rehearse=args.rehearse, t_start=t_start)
    except (SpecError, device.NoChip) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2

