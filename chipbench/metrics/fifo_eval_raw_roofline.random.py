"""Share of the HBM roofline in k15mmtree_relu.random: the raw kernel (safe rung and raw backstop)."""

from bench.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "fifo_eval_raw")
