"""Graph500's rate: the directed edges whose source each of the
window's solves reached (its connected component, from the reference),
summed, over the window's seconds on the host clock."""

from bench import work


def read(run):
    return work.teps(run.reached()[0], run.window.seconds)
