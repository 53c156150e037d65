"""Consecutive campaigns, what ``python -m repro.launch.campaign`` runs:
``Campaign(spec).run()`` with seed ``--seed + i`` until the first
campaign boundary after ``--seconds``.

Mix parameters: ``optimizers``, ``budget``, ``max_rows`` (the largest
round, every design's largest batch together, which bounds the shapes
to warm up), ``warmup_budget`` (the set-up's one campaign).

Set-up traces the deployment's designs and builds the cross-design
dispatcher once; each campaign of the window reuses them with empty
evaluation caches, where the command line would trace them again.
"""

from __future__ import annotations

import contextlib
import time

from bench import instrument, warm


def _spec(ctx, seed: int, budget: int):
    from repro.core.campaign import CampaignSpec
    from repro.core.config import EvalConfig
    dep = ctx.deployment
    return CampaignSpec(designs=tuple(dep["designs"]),
                        optimizers=tuple(ctx.mix["optimizers"]),
                        budget=budget, seed=seed,
                        eval=EvalConfig(**dep["eval"]), workers=0,
                        hetero=True)


@contextlib.contextmanager
def _reusing(system):
    """Campaigns built in here take the set-up's design contexts (with
    fresh caches) and cross-design dispatcher instead of new ones."""
    import repro.core.backends.dispatch as dispatch
    import repro.core.campaign.scheduler as scheduler
    from repro.core.backends import ConfigCache
    contexts, hd = system
    for dctx in contexts.values():
        dctx.advisor.cache = ConfigCache(dctx.graph.n_fifos)
    saved = scheduler.DesignContext, dispatch.HeteroDispatcher
    scheduler.DesignContext = lambda name, spec: contexts[name]
    dispatch.HeteroDispatcher = lambda *a, **k: hd
    try:
        yield
    finally:
        scheduler.DesignContext, dispatch.HeteroDispatcher = saved


def setup(ctx):
    from repro.core.campaign import Campaign
    camp = Campaign(_spec(ctx, ctx.warmup_seed, ctx.mix["warmup_budget"]))
    hd, contexts = camp.hetero, camp.designs
    instrument.instrument_hetero(
        hd, ctx.tallies, {k: d.graph.n_events for k, d in contexts.items()})
    first = next(iter(contexts))
    row = contexts[first].advisor.baseline_max.depths
    hd.chipbench_row_bytes = warm.stacked_row_bytes(hd, row, first)
    warm.hetero(hd, ctx.mix["max_rows"], row, first)
    camp.run()
    return contexts, hd


def window(system, ctx, seconds: float) -> dict:
    import jax
    from repro.core.campaign import Campaign
    contexts, hd = system
    before = dict(vars(hd.stats))
    answers, rows, n = [], 0, 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while True:
            with _reusing(system):
                camp = Campaign(_spec(ctx, ctx.seed + n, ctx.mix["budget"]))
            instrument.wrap(camp, "_round", "campaign.round")
            with jax.profiler.TraceAnnotation("chipbench.campaign"):
                store = camp.run()
            n += 1
            for key in store.keys():
                dse = store[key]
                answers.append((dse.design_name, dse))
                rows += dse.result.configs.shape[0]
            if time.perf_counter() - t0 >= seconds:
                break
    elapsed = time.perf_counter() - t0
    hs = {k: v - before[k] for k, v in vars(hd.stats).items()}
    tasks = len(ctx.deployment["designs"]) * len(ctx.mix["optimizers"])
    return {
        "t0": t0, "elapsed_s": elapsed, "answers": answers,
        "attempted": n * tasks, "failed": n * tasks - len(answers),
        "metrics": {"campaign_configs_per_s": rows / elapsed},
        "counters": {"campaigns": n, "rows_delivered": rows,
                     "row_bytes": hd.chipbench_row_bytes,
                     **{"hetero_" + k: v for k, v in hs.items()}},
    }
