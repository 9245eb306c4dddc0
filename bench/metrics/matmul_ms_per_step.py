"""Device time of the step's matmul-class ops per window step, averaged over
the cell's chips (profiler trace; ops classed from the compiled step's HLO
by ``trace.hlo_categories``).  Nothing to read when the class took no time."""


def read(run):
    if not run.devices or not run.window_steps:
        return None
    ns = sum(w.by_category["matmul"] for w in run.devices.values()) / len(run.devices)
    return ns / 1e6 / run.window_steps if ns > 0 else None
