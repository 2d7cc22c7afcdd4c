"""Streamed whole-subspace passes (`IOStats.passes`) per operator apply."""
from bench import readers


def read(run):
    return readers.per_expansion(run, "passes")
