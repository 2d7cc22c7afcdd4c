"""Least time of the traced `spmm_blocksparse` calls over their summed
device time, in percent. At about 2 flops per byte the bytes bound
decides: image, operand and output at the HBM bandwidth."""
from bench import readers


def read(run):
    return readers.roofline_share(run, ["spmm_blocksparse"])
