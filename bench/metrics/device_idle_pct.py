"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (profiler trace)."""


def read(run):
    return None if run.trace is None else run.trace.idle_pct
