"""Device milliseconds per superstep in the engine's ``relax`` scope
(the push relax and the dense fallback, each with its scatter), a mean
per chip, over the traced window's supersteps."""

from bench import scopes


def read(run):
    return scopes.ms_per_superstep(run, ("relax",))
