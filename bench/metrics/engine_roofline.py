"""Share of the HBM roofline of the device work that served the solves:
the least bytes any exact SSSP moves for the edges and vertices the
window's solves reached (``bench.work``), at the chips' published HBM
bandwidth, over the device's busy time in the traced window."""

from bench import work


def read(run):
    if run.trace is None:
        return None
    edges, vertices = run.reached()
    chips = run.trace.devices
    return work.roofline_pct(edges, vertices, run.trace.busy_s,
                             chips * run.peaks["hbm_bytes_per_s"])
