"""Seconds per whole solve: the window, up to the end of its last solve,
over the solves in it (host clock)."""


def read(run):
    if run.mode != "solves" or not run.results:
        return None
    return run.window_s / len(run.results)
