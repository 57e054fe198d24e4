"""Share of the inter-chip (ICI) roofline that the engine's collectives
reach: the least time the bytes each chip sent to the others in the
traced window take at the chips' published ICI bandwidth
(``bench.collectives``: all-to-all and all-reduce operands less the
block a chip keeps, times each one's runs in the trace, so it reads
whichever exchange each superstep ran), over the device time of the
collectives, a mean per chip.  Only a run over more than one chip has
such operations to read."""

from bench import collectives


def read(run):
    trace = run.trace
    if (trace is None or trace.devices < 2 or trace.collective_s <= 0
            or "ici_bits_per_s" not in run.peaks):
        return None
    sent = collectives.bytes_sent(run)
    if not sent:
        return None
    least_s = sent / (run.peaks["ici_bits_per_s"] / 8)
    return 100.0 * least_s / trace.collective_s
