"""Host ms per cross-design dispatch to stack, pad and send the batch
until the launch is queued (HeteroStats prep_s: fifo.hetero.stack,
.pad and .h2d)."""

from bench.program import ratio


def read(run):
    return ratio(run, "hetero_prep_s", "hetero_n_dispatches", 1e3)
