"""Mean ``QueryStats.plan_s`` of the window's answered requests."""


def reduce(view):
    reqs = view.get("requests", ())
    return sum(r["plan_s"] for r in reqs) / len(reqs) if reqs else None
