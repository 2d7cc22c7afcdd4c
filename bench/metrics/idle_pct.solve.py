"""Share of the traced window in which no operation ran on the device,
in the whole-solve cells: 1 - union of device op intervals / window."""
from bench import readers


def read(run):
    return readers.idle_pct(run, "solves")
