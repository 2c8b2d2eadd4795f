"""Host batch preparation per step: the benchmark's ``bench.side_at`` span
around the program's feed (``StepSetup.side_at``: the batch made into the
step's side inputs and put on the mesh), mean over the traced steps, in ms."""

import trace_reduce


def read(run):
    if run.trace is None:
        return None
    spans = trace_reduce.span_seconds(run.trace, "bench.side_at")
    return 1e3 * sum(spans) / len(spans) if spans else None
