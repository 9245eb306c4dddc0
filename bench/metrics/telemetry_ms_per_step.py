"""Device time of the ops under the step's ``telemetry`` named scope per window
step, averaged over the cell's chips; step telemetry: ``auc_proxy``,
``collection.metrics`` and the plan's id-level hit probe.  Profiler trace; ops
are put under scopes by ``layers.hlo_scopes`` from the compiled step's HLO
metadata.  Nothing to read when the program carries no scopes."""
from bench import layers


def read(run):
    return layers.scope_ms_per_step(run, "telemetry")
