"""Device time of the ops under the ``B`` scope (the executor's
input-gradient passes), ms per step, mean over chips."""

import scoped


def read(run):
    return scoped.reading(run, lambda s: scoped.scope_ms(s, "B"))
