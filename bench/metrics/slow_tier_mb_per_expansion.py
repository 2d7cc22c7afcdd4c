"""Bytes the tiered store read from its slow tier (`IOStats.
host_bytes_read`) per operator apply, in MB. The operator is built
without the store, so the device-resident matrix image is not counted."""
from bench import readers


def read(run):
    v = readers.per_expansion(run, "host_bytes_read")
    return None if v is None else v / 1e6
