"""Thick restarts per whole solve (`EigResult.n_restarts`), averaged over
the window's solves."""


def read(run):
    if run.mode != "solves" or not run.results:
        return None
    return sum(r.n_restarts for r in run.results) / len(run.results)
