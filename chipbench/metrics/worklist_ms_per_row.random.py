"""Host worklist ms per escalated row in k15mmtree_relu.random
(BatchStats worklist_s, the fifo.worklist span, over n_fallbacks)."""

from bench.program import ratio


def read(run):
    return ratio(run, "worklist_s", "n_fallbacks", 1e3)
