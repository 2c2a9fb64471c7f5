"""idle_share.refresh: 1 − (union of device-busy intervals) / (traced
window), in percent, over the traced segment's refreshes: the patch, the
solve and the reads of each, and the traffic's draw between them."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
