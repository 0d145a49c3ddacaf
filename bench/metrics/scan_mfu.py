"""The whole query path's share (%) of the chip's peak: the least time
of the window's answered queries (``least_s``, as ``scan_roofline``
counts it) over the window's wall-clock seconds.  Unlike
``scan_roofline`` it counts the device's idle time, so it bounds any
gain that only moves work off the device."""


def reduce(view):
    queries, window_s = view.get("queries", ()), view.get("window_s")
    if not queries or not window_s:
        return None
    return 100.0 * sum(q["least_s"] for q in queries) / window_s
