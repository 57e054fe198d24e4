"""Seconds JAX spent tracing, lowering, compiling or reading the
persistent cache during set-up (its own compile events)."""


def read(run):
    return run.setup.compile_s
