"""Transmitter rounds a window step runs to load its plan's rows (the
write-back runs as many): the window delta of the trainer hub's exact counter
``cache_move_rounds`` over the window's steps.  On four chips the shards share
one loop, so the counter takes the busiest shard's rounds.  Nothing to read
when the program has no ``cache_move_rounds`` counter."""
from bench import layers


def read(run):
    if not run.window_steps:
        return None
    rounds = layers.window_counter(run, "cache_move_rounds")
    if rounds is None:
        return None
    return rounds / run.window_steps
