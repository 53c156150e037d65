"""Jacobi iterations per row of the raw kernel in k15mmtree_relu.sa
(BatchStats raw_row_iters / raw_rows, the kernel's lane 3)."""

from bench.program import ratio


def read(run):
    return ratio(run, "raw_row_iters", "raw_rows")
