"""The pipeline's bubble on the chip, in %: per chip, the share of the
window in which no op but a collective of the pipe (``send``, ``sync``)
runs, mean over chips.  None where the step sends nothing."""

import scoped


def read(run):
    return scoped.reading(run, scoped.stage_idle_share)
