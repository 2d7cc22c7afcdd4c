"""Share of the tiered store's block reads served from HBM, in percent:
`IOStats.cache_hits` over `cache_hits + cache_misses`, summed over the
window's whole solves. A miss is a read from the slow tier."""


def read(run):
    if run.mode != "solves" or not run.io:
        return None
    hits = sum(io["cache_hits"] for io in run.io)
    reads = hits + sum(io["cache_misses"] for io in run.io)
    return 100.0 * hits / reads if reads else None
