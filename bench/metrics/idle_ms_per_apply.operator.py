"""Device idle milliseconds per operator apply in the traced window while
the host was in `operator.matmat` (block SpMM and the COO side path's
dispatch), outside its store calls (`bench/hostspans.py`)."""
from bench import hostspans


def read(run):
    return hostspans.idle_ms_per_apply(run, "operator")
