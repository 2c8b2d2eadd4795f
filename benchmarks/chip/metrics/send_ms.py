"""Device time of the ops under the ``send`` scope (the pipe's collective
permutes: the transfer and the wait for the neighbour), ms per step, mean
over chips.  None where the step sends nothing."""

import scoped


def read(run):
    return scoped.reading(run, lambda s: scoped.scope_ms(s, "send"))
