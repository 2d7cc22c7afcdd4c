"""Device idle milliseconds per operator apply in the traced window while
the host was in the restart loop: under the `solve` span and no store,
pass or operator span (H assembly, `eigh`, host syncs of the expansion's
results) (`bench/hostspans.py`)."""
from bench import hostspans


def read(run):
    return hostspans.idle_ms_per_apply(run, "restart")
