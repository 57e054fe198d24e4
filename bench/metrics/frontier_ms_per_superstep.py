"""Device milliseconds per superstep in the engine's ``eligibility``,
``compact`` and ``vote`` scopes (the class fold and commit, the row
compaction, the fold into the pending state and the votes), a mean per
chip, over the traced window's supersteps."""

from bench import scopes


def read(run):
    return scopes.ms_per_superstep(run, ("eligibility", "compact", "vote"))
