"""Device time of the ops under the ``W`` scope (the executor's
weight-gradient passes), ms per step, mean over chips."""

import scoped


def read(run):
    return scoped.reading(run, lambda s: scoped.scope_ms(s, "W"))
