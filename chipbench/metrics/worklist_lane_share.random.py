"""Share of the rows escalated to the host worklist in
k15mmtree_relu.random that its worklist lanes solved, off the thread that
drives the chip (BatchStats worklist_lane_rows over n_fallbacks)."""

from bench.program import ratio


def read(run):
    return ratio(run, "worklist_lane_rows", "n_fallbacks", 100.0)
