"""Self time of the step's device ops under none of the known scopes
(``scoped.known_scopes``: the program's and those the readers state), ms
per step, mean over chips: the executor's copies and relayouts that no
scope names."""

import scoped


def read(run):
    return scoped.reading(run, scoped.unscoped_ms)
