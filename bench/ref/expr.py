"""The traffic files' expression language, evaluated with plain numpy.

An expression is one of:

- a column name, such as ``"l_shipdate"``;
- a parameter, ``"$delta"``, drawn per stream by the query driver;
- a number, or ``{"date": "YYYY-MM-DD"}`` for that day's number
  (days since 1970-01-01);
- ``[op, left, right]`` with ``op`` one of ``+ - * / < <= > >= == !=
  & |``.

``resolve`` substitutes parameters and dates and folds constants;
``evaluate`` computes a resolved expression over numpy columns.
"""
from __future__ import annotations

import datetime as _dt
import operator
from typing import Any, Callable, Dict, Set

import numpy as np

OPS: Dict[str, Callable] = {
    "+": operator.add, "-": operator.sub, "*": operator.mul,
    "/": operator.truediv, "<": operator.lt, "<=": operator.le,
    ">": operator.gt, ">=": operator.ge, "==": operator.eq,
    "!=": operator.ne, "&": operator.and_, "|": operator.or_,
}
EPOCH = _dt.date(1970, 1, 1)


def resolve(expr: Any, params: Dict[str, Any]) -> Any:
    """Substitute ``$param``s and dates, folding constant subtrees."""
    if isinstance(expr, dict):
        if set(expr) != {"date"}:
            raise ValueError(f"bad expression {expr!r}")
        return (_dt.date.fromisoformat(expr["date"]) - EPOCH).days
    if isinstance(expr, str):
        if expr.startswith("$"):
            return params[expr[1:]]
        return expr
    if isinstance(expr, (list, tuple)):
        if len(expr) != 3 or expr[0] not in OPS:
            raise ValueError(f"bad expression {expr!r}")
        left, right = resolve(expr[1], params), resolve(expr[2], params)
        if _is_const(left) and _is_const(right):
            return OPS[expr[0]](left, right)
        return [expr[0], left, right]
    if _is_const(expr):
        return expr
    raise ValueError(f"bad expression {expr!r}")


def _is_const(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def columns(expr: Any) -> Set[str]:
    """Column names a resolved expression reads."""
    if isinstance(expr, str):
        return {expr}
    if isinstance(expr, list):
        return columns(expr[1]) | columns(expr[2])
    return set()


def count_ops(expr: Any) -> int:
    """Arithmetic and comparison operations per row."""
    if isinstance(expr, list):
        return 1 + count_ops(expr[1]) + count_ops(expr[2])
    return 0


def evaluate(expr: Any, getcol: Callable[[str], np.ndarray],
             const: Callable[[Any], Any] = lambda v: v):
    """Evaluate a resolved expression; ``getcol(name)`` gives a column and
    ``const`` converts a literal (the bfloat16 control rounds both)."""
    if isinstance(expr, str):
        return getcol(expr)
    if isinstance(expr, list):
        return OPS[expr[0]](evaluate(expr[1], getcol, const),
                            evaluate(expr[2], getcol, const))
    return const(expr)
