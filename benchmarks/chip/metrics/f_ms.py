"""Device time of the ops under the ``F`` scope (the executor's forward
passes, the head's forward among them), ms per step, mean over chips."""

import scoped


def read(run):
    return scoped.reading(run, lambda s: scoped.scope_ms(s, "F"))
