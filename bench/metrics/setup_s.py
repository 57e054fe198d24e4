"""Seconds from process start to the window's start (host clock):
imports and chip start-up, loading the graph, partition and placement,
the engine's compile or cache read, and the warm-up."""


def read(run):
    return run.setup_s
