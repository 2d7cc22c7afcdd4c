"""Least time of the traced `gram` and `tsgemm` calls (the
orthogonalization's tall-skinny products) over their summed device time,
in percent; bytes-bound like the SpMM."""
from bench import readers


def read(run):
    return readers.roofline_share(run, ["gram", "tsgemm"])
