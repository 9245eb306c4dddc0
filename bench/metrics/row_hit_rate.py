"""Unique rows found resident over unique rows asked in the window, %: 100 x
(1 - misses / uniq_rows) from the window deltas of the trainer hub's exact
counters ``cache_misses`` and ``cache_uniq_rows``, both counted in unique
rows per plan (one chip or per shard alike).  Nothing to read when the
program has no ``cache_uniq_rows`` counter."""
from bench import layers


def read(run):
    if run.misses is None:
        return None
    rows = layers.window_counter(run, "cache_uniq_rows")
    if not rows:
        return None
    return 100.0 * (1.0 - run.misses / rows)
