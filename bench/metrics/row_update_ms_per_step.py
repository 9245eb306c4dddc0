"""Device time of the ops under the step's ``cache.row_update`` named scope per
window step, averaged over the cell's chips; the row update, ``apply_grads``
on the resident rows.  Profiler trace; ops are put under scopes by
``layers.hlo_scopes`` from the compiled step's HLO metadata.  Nothing to read
when the program carries no scopes."""
from bench import layers


def read(run):
    return layers.scope_ms_per_step(run, "cache.row_update")
