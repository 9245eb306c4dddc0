"""Device time of the ops under the step's ``cache.lookup`` named scope per
window step, averaged over the cell's chips; the arena lookup and its
transpose, without the ops of the ``shard.exchange`` scope nested in it (an op
counts under its innermost scope).  Profiler trace; ops are put under scopes
by ``layers.hlo_scopes`` from the compiled step's HLO metadata.  Nothing to
read when the program carries no scopes."""
from bench import layers


def read(run):
    return layers.scope_ms_per_step(run, "cache.lookup")
