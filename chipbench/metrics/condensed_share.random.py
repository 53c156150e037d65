"""Share of the distinct rows the evaluator solved that a condensed rung
resolved (BatchStats n_condensed)."""

from bench.readers import rung_share


def read(run):
    return rung_share(run, "n_condensed")
