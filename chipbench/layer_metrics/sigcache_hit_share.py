"""Share of cache probes answered by the verified-signature cache or
the commit memo over the window (sigcache.stats() deltas). A cold cell
reads 0; it is what shows that a later warm cell is warm."""


def read(ctx):
    c = ctx.counters
    hits = c["cache_hits"] + c["memo_hits"]
    probes = hits + c["cache_misses"] + c["memo_misses"]
    if not probes:
        return None
    return 100.0 * hits / probes
