"""Supersteps per solve, the engine's own count (``WorkMetrics``),
averaged over the window's solves that reached their fixpoint."""


def read(run):
    steps = [r.supersteps for r in run.window.solves
             if r.converged and not r.error]
    return sum(steps) / len(steps) if steps else None
