"""Bytes the store's device budget moved to the slow tier
(`IOStats.evict_bytes`) per whole solve, in MB. None where the program
does not count them."""


def read(run):
    if run.mode != "solves" or not run.io or "evict_bytes" not in run.io[0]:
        return None
    return sum(io["evict_bytes"] for io in run.io) / len(run.io) / 1e6
