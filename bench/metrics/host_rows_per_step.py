"""Rows that cross the host link per window step: the window delta of the
trainer hub's exact counter ``host_moved_rows`` (each plan's misses, loaded
from the slow tier, plus its write-backs) over the window's steps.  Nothing
to read when the program has no ``host_moved_rows`` counter."""
from bench import layers


def read(run):
    if not run.window_steps:
        return None
    rows = layers.window_counter(run, "host_moved_rows")
    if rows is None:
        return None
    return rows / run.window_steps
