"""Host ms per cross-design dispatch from the queued launch until its
results are on the host (HeteroStats wait_s: fifo.hetero.wait)."""

from bench.program import ratio


def read(run):
    return ratio(run, "hetero_wait_s", "hetero_n_dispatches", 1e3)
