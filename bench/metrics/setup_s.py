"""Seconds from process start to window start: generating and packing
the graph, moving it to the device and the warm-up solve with its
compiles (host clock)."""


def read(run):
    return run.setup_s
