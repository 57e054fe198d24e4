"""Device milliseconds per superstep: the device's busy time in the
traced window (mean per chip) over the supersteps its solves ran."""


def read(run):
    steps = sum(r.supersteps for r in run.window.solves if not r.error)
    if run.trace is None or steps == 0 or run.trace.busy_s <= 0:
        return None
    return 1e3 * run.trace.busy_s / steps
