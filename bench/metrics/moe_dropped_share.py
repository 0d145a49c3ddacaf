"""Share (%) of the window's assignments to this chip's experts that
were over capacity and dropped: the program's ``moe_dropped`` over
``moe_assigned`` counters, summed over the window's steps."""


def reduce(view):
    assigned = view.get("moe_assigned")
    if not assigned:
        return None
    return 100.0 * view["moe_dropped"] / assigned
