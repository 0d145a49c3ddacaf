"""Bytes the store read (``QueryStats.bytes_scanned``) per table row a
window request covered."""


def reduce(view):
    reqs = view.get("requests", ())
    rows = sum(r["rows"] for r in reqs)
    return sum(r["bytes_scanned"] for r in reqs) / rows if rows else None
