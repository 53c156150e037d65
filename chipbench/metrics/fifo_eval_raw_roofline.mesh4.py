"""Share of the HBM roofline in campaign.fast10.mesh4: the raw kernel on
per-row tables, its device time summed over the four chips (so the bytes
are held against one chip's peak)."""

from bench.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "fifo_eval_raw")
