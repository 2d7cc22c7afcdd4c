"""Device idle milliseconds per operator apply in the traced window while
the host was in the tiered store: a `store.*` span (the SAFS page path
under `TieredStore.get`/`put`/`demote`/`delete`/`host_pin`/`prefetch`/
`close`) or a `safs.*` span on the main thread (`bench/hostspans.py`)."""
from bench import hostspans


def read(run):
    return hostspans.idle_ms_per_apply(run, "store")
