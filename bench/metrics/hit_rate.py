"""Cache hits over hits plus misses in the window, from the deltas of the
trainer hub's exact counters (``cache_hits``, ``cache_misses``)."""


def read(run):
    if run.hits is None or run.misses is None or run.hits + run.misses == 0:
        return None
    return 100.0 * run.hits / (run.hits + run.misses)
