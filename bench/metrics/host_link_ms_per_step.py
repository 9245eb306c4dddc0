"""Device time of the ops under the step's ``cache.host_link`` named scope per
window step, averaged over the cell's chips: the legs where a host-resident
slow tier's rows cross the host link (the row DMAs between host memory and
the device and the loops that issue them).  Profiler trace; the ops are put
under the scope by ``host_link.link_ops`` from the compiled step's HLO
metadata.  Nothing to read when the program has no such scope."""
from bench import host_link


def read(run):
    return host_link.host_link_ms_per_step(run)
