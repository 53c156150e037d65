"""Share of the raw kernel's rows in k15mmtree_relu.random whose 8-row
block replayed its gather schedules instead of walking them each
iteration (BatchStats raw_gather_fallbacks over raw_rows, the kernel's
lane 4)."""

from bench.program import ratio


def read(run):
    walked = ratio(run, "raw_gather_fallbacks", "raw_rows", 100.0)
    return None if walked is None else 100.0 - walked
