"""Share (%) of the device's busy time in the traced window that the
window's answered queries need at the least: the sum of each query's
least time (``least_s``: its logical bytes over the HBM bandwidth or its
logical operations over the peak rate, whichever is longer, from
``bench.drivers.query.logical_cost``) over the busy seconds of the
trace.  It measures the same work whatever runs it; today the bytes
bound it."""


def reduce(view):
    trace, queries = view["trace"], view.get("queries", ())
    if trace.busy_s <= 0 or not queries:
        return None
    return 100.0 * sum(q["least_s"] for q in queries) / trace.busy_s
