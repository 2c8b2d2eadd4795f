"""Device time of the ops under the ``head_ce`` scope (the LM head and
its cross-entropy, forward and backward: inside ``F``, ``B`` and ``W``),
ms per step, mean over chips."""

import scoped


def read(run):
    return scoped.reading(run, lambda s: scoped.scope_ms(s, "head_ce"))
