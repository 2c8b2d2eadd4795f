"""Share of the traced window in which no operation ran on a chip, in %,
averaged over the chips: 1 - (union of device-op intervals) / window."""

import trace_reduce


def read(run):
    if run.trace is None:
        return None
    busy = trace_reduce.busy_seconds(run.trace)
    if busy is None:
        return None
    t0, t1 = trace_reduce.window(run.trace)
    return 100.0 * (1.0 - busy / ((t1 - t0) / 1e9))
