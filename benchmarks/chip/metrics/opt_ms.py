"""Device time of the ops under the ``opt`` scope (the closing reductions
of the gradients and the loss, gradient statistics, AdamW and
post-validation), ms per step, mean over chips."""

import scoped


def read(run):
    return scoped.reading(run, lambda s: scoped.scope_ms(s, "opt"))
