"""90th percentile of the trainer's per-step host-clock times over the traced
window (dispatch to loss ready, as ``Trainer`` records them)."""
import statistics


def read(run):
    if len(run.step_times_s) < 2:
        return None
    return 1e3 * statistics.quantiles(run.step_times_s, n=10)[8]
