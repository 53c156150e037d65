"""Share of the HBM roofline in k15mmtree_relu.sa: the fused condensed
kernel, which each search's baseline pair reaches (the random cell's
uniform rows never land in a rung's box, so it has no condensed time)."""

from bench.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "fifo_eval_condensed")
