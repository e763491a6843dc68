"""Share of the traced window in which no operation ran on the device:
1 - (union of the device ops' intervals) / window, from the profiler trace."""


def read(r):
    if r.trace is None or r.trace.n_devices == 0:
        return None
    return 100.0 * r.trace.idle_share
