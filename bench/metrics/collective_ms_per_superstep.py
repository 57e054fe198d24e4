"""Device milliseconds per superstep spent in operations that move data
between chips (all-to-all, all-reduce and the like, by HLO opcode), a
mean over the chips, in the traced window.  Only a run over more than
one chip has such operations to read."""


def read(run):
    steps = sum(r.supersteps for r in run.window.solves if not r.error)
    if run.trace is None or run.trace.devices < 2 or steps == 0:
        return None
    return 1e3 * run.trace.collective_s / steps
