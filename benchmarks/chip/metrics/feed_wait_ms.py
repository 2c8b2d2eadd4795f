"""Time the chip idles inside the program's ``repro.feed`` spans (the
batch made, built into the step's side inputs and put on the mesh), ms
per step, mean over chips."""

import scoped


def read(run):
    return scoped.reading(run, scoped.feed_wait_ms)
