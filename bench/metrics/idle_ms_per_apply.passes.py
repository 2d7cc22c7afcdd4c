"""Device idle milliseconds per operator apply in the traced window while
the host was in the subspace passes: `ortho.bcgs2` or `pass.subspace`
(Gram/TSGEMM dispatch, zero-padding, CholQR) outside their store calls
(`bench/hostspans.py`)."""
from bench import hostspans


def read(run):
    return hostspans.idle_ms_per_apply(run, "passes")
