"""Device milliseconds per superstep in the engine's ``exchange`` scope
(the sparse payload, its vote and all-to-all, the combine, or the
dense exchange), a mean per chip, over the traced window's
supersteps."""

from bench import scopes


def read(run):
    return scopes.ms_per_superstep(run, ("exchange",))
