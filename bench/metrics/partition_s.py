"""Host seconds of ``Solver.partition``: partitioning the graph into the
engine's ELL buffers and placing them on the chips (host clock)."""


def read(run):
    return run.setup.partition_s
