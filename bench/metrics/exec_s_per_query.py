"""Mean ``QueryStats.exec_s`` (partition execution: store reads, host
work, kernels) of the window's answered requests."""


def reduce(view):
    reqs = view.get("requests", ())
    return sum(r["exec_s"] for r in reqs) / len(reqs) if reqs else None
