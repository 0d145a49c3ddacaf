"""Plain numpy reference of a grouped filter -> aggregate query.

``grouped`` computes, over whole columns, every aggregate of a query
resolved by ``bench.ref.expr.resolve``: rows pass the filter, fall into
groups by the key expression, and each aggregate (``sum``, ``mean`` or
``count``) is taken per group, in float64.  Only groups with at least
one row are returned, keys ascending.

``precision="bfloat16"`` is the lower-precision control: every float32
column and every value computed from them per row is rounded to
bfloat16, and each result is rounded to bfloat16 (integer columns, the
filter, the key and counts stay exact).
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from bench.ref.expr import evaluate

Result = Tuple[np.ndarray, np.ndarray]          # (keys, values)


def grouped(cols: Dict[str, np.ndarray], query: Dict,
            precision: str = "float64") -> Dict[str, Result]:
    if precision == "float64":
        fdt = np.dtype(np.float64)
    elif precision == "bfloat16":
        import ml_dtypes
        fdt = np.dtype(ml_dtypes.bfloat16)
    else:
        raise ValueError(f"unknown precision {precision!r}")

    def getcol(name: str) -> np.ndarray:
        c = cols[name]
        return (c.astype(fdt) if np.issubdtype(c.dtype, np.floating)
                else c.astype(np.int64))

    n = len(next(iter(cols.values())))
    keep = (np.ones(n, bool) if query.get("filter") is None
            else np.asarray(evaluate(query["filter"], getcol), bool))
    key = np.asarray(evaluate(query["group"], getcol)).astype(np.int64)
    keys, inv = np.unique(key[keep], return_inverse=True)
    counts = np.bincount(inv, minlength=len(keys)).astype(np.int64)
    out: Dict[str, Result] = {}
    for agg in query["aggregates"]:
        if agg["agg"] == "count":
            out[agg["name"]] = (keys, counts)
            continue
        vals = np.broadcast_to(evaluate(agg["value"], getcol), (n,))[keep]
        sums = np.bincount(inv, weights=vals.astype(np.float64),
                           minlength=len(keys))
        if agg["agg"] == "mean":
            res = sums / counts
        elif agg["agg"] == "sum":
            res = sums
        else:
            raise ValueError(f"unsupported aggregate {agg['agg']!r}")
        out[agg["name"]] = (keys, res.astype(fdt).astype(np.float64))
    return out


def compare(got: Result, want: Result, agg: str) -> Dict[str, float]:
    """How one answer departs from the reference: ``groups`` is 1 when the
    group keys differ (and then nothing else is read), ``count`` the
    largest absolute count difference, ``rel`` the largest relative
    difference of a float aggregate."""
    gk, gv = (np.asarray(x) for x in got)
    wk, wv = want
    if gk.shape != wk.shape or not np.array_equal(gk.astype(np.int64), wk):
        return {"groups": 1.0}
    if agg == "count":
        return {"groups": 0.0, "count": float(np.max(np.abs(
            gv.astype(np.int64) - wv), initial=0))}
    rel = np.abs(gv.astype(np.float64) - wv) / np.maximum(np.abs(wv),
                                                          1e-300)
    return {"groups": 0.0, "rel": float(np.max(rel, initial=0.0))}
