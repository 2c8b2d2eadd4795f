"""Device time of the ops under the ``attn`` scope (q, k, v to the
attention output, forward and backward: inside ``F`` and ``B``), ms per
step, mean over chips."""

import scoped


def read(run):
    return scoped.reading(run, lambda s: scoped.scope_ms(s, "attn"))
