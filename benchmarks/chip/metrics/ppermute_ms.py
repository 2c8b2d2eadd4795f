"""Device time inside collective-permute ops per step, in ms, averaged over
the chips.  A stage that waits for its neighbour waits inside the op, so the
pipeline's bubble and its transfers both show here.  None where the program
sends nothing between chips."""

import trace_reduce


def read(run):
    if run.trace is None or run.steps == 0:
        return None
    s = trace_reduce.op_seconds(run.trace, r"collective-permute")
    return None if s is None else 1e3 * s / run.steps
