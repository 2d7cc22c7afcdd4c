"""Seconds per operator apply in capped solves: the window, up to the end
of its last capped solve, over the applies (`EigResult.n_ops`) in it
(host clock)."""


def read(run):
    if run.mode != "capped" or not run.n_ops:
        return None
    return run.window_s / run.n_ops
