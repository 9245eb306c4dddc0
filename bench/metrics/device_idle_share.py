"""Share of the traced window in which no op ran on the device, averaged
over the cell's chips (profiler trace)."""


def read(run):
    if not run.devices:
        return None
    busy = sum(w.busy_ns / w.window_ns for w in run.devices.values()) / len(run.devices)
    return 100.0 * (1.0 - busy)
