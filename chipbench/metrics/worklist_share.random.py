"""Share of the distinct rows the evaluator solved that escalated to the
host worklist (BatchStats n_fallbacks)."""

from bench.readers import rung_share


def read(run):
    return rung_share(run, "n_fallbacks")
