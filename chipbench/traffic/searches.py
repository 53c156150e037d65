"""Closed-loop searches on one advisor: one ``FifoAdvisor.run`` after
another, each with seed ``--seed + i`` and an empty evaluation cache,
until the first search boundary after ``--seconds``.

Mix parameters: ``optimizer``, ``budget``, ``max_rows`` (the largest
batch the optimizer sends, which bounds the shapes to warm up), and
optionally ``pool`` with ``pool_seed``: then every run's searches use
the same ``pool`` search seeds, ``pool_seed + k``, in an order the run's
seed shuffles, cycling through them; every seed then offers the same
work in another order.
"""

from __future__ import annotations

import time

import numpy as np

from bench import instrument, warm


def setup(ctx):
    from repro.core import FifoAdvisor
    from repro.core.config import EvalConfig
    from repro.designs import make_design
    dep = ctx.deployment
    adv = FifoAdvisor(make_design(dep["design"]),
                      EvalConfig(**dep["eval"]))
    instrument.instrument_evaluator(adv.evaluator, ctx.tallies)
    warm.evaluator(adv.evaluator, ctx.mix["max_rows"],
                   adv.baseline_max.depths)
    _search(adv, ctx.mix, ctx.warmup_seed)
    return adv


def _search(adv, mix, seed):
    from repro.core.backends import ConfigCache
    adv.cache = ConfigCache(adv.graph.n_fifos)
    return adv.run(mix["optimizer"], budget=mix["budget"], seed=seed)


def search_seeds(mix: dict, seed: int):
    """The search seed of each search of the window, in order."""
    if "pool" not in mix:
        return lambda i: seed + i
    order = np.random.default_rng([abs(int(seed)), 0x5EA]).permutation(
        mix["pool"])
    return lambda i: int(mix["pool_seed"]) + int(order[i % len(order)])


def window(adv, ctx, seconds: float) -> dict:
    import jax
    from repro.kernels.fifo_eval.ops import DISPATCH_COUNTS
    ev = adv.evaluator
    before = dict(vars(ev.stats))
    DISPATCH_COUNTS.clear()
    answers, rows = [], 0
    seed_of = search_seeds(ctx.mix, ctx.seed)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while True:
            with jax.profiler.TraceAnnotation("chipbench.search"):
                dse = _search(adv, ctx.mix, seed_of(len(answers)))
            answers.append((adv.design.name, dse))
            rows += dse.result.configs.shape[0]
            if time.perf_counter() - t0 >= seconds:
                break
    elapsed = time.perf_counter() - t0
    stats = {k: v - before[k] for k, v in vars(ev.stats).items()}
    n = len(answers)
    return {
        "t0": t0, "elapsed_s": elapsed, "answers": answers,
        "attempted": n, "failed": 0,
        "metrics": {"configs_per_s": rows / elapsed, "search_s": elapsed / n},
        "counters": {"searches": n, "rows_delivered": rows,
                     "dispatches": dict(DISPATCH_COUNTS), **stats},
    }
