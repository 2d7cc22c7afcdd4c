"""Device memory at its peak, read after the window
(`memory_stats()["peak_bytes_in_use"]`), in GB."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 1e9
