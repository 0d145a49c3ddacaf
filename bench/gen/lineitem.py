"""Seeded generator of the TPC-H ``lineitem`` table (TPC-H spec v3, 4.2.3).

Columns are generated with the spec's own distributions, one numpy array
per column, in order-key order as dbgen writes them:

- order date uniform over [1992-01-01, ENDDATE - 151 days = 1998-08-02];
- 1..7 lines per order; order keys sparse as dbgen's (the first 8 of
  every 32 keys);
- ship date = order date + U[1, 121], commit date = order date + U[30, 90],
  receipt date = ship date + U[1, 30];
- line status 'O' iff ship date > CURRENTDATE (1995-06-17), else 'F';
- return flag 'R' or 'A' at random iff receipt date <= CURRENTDATE,
  else 'N';
- quantity U[1, 50], discount U[0.00, 0.10], tax U[0.00, 0.08] in
  steps of 0.01;
- part key U[1, SF * 200,000]; supplier key by dbgen's rule for the
  part's four suppliers over S = SF * 10,000 suppliers;
- extended price = quantity * the part's retail price,
  (90000 + ((partkey / 10) mod 20001) + 100 * (partkey mod 1000)) / 100.

Dates are int32 day numbers (days since 1970-01-01); the enumerations
are int32 codes in the order of the configuration's ``codes`` lists;
``decimal(15,2)`` columns are float32.  ``l_comment`` is not generated.
"""
from __future__ import annotations

import datetime as _dt
from typing import Dict, List

import numpy as np

EPOCH = _dt.date(1970, 1, 1)
ORDERS_PER_SF = 1_500_000
PARTS_PER_SF = 200_000
SUPPLIERS_PER_SF = 10_000


def day_number(iso: str) -> int:
    """'YYYY-MM-DD' -> days since 1970-01-01."""
    return (_dt.date.fromisoformat(iso) - EPOCH).days


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE of each part key, in currency units (float64)."""
    pk = partkey.astype(np.int64)
    return (90000 + (pk // 10) % 20001 + 100 * (pk % 1000)) / 100.0


def supplier_key(partkey: np.ndarray, i: np.ndarray, suppliers: int
                 ) -> np.ndarray:
    """dbgen's i-th (0..3) supplier of each part key over ``suppliers``."""
    pk = partkey.astype(np.int64)
    s = np.int64(suppliers)
    return ((pk + i * (s // 4 + (pk - 1) // s)) % s + 1).astype(np.int32)


def generate(cfg: Dict, seed: int) -> Dict[str, np.ndarray]:
    """Every column of ``cfg["columns"]`` for ``cfg["scale_factor"]``,
    keyed by column name; the same ``seed`` gives the same table."""
    sf = float(cfg["scale_factor"])
    codes = cfg["codes"]
    rng = np.random.default_rng(seed)
    n_orders = int(round(sf * ORDERS_PER_SF))
    parts = int(round(sf * PARTS_PER_SF))
    suppliers = int(round(sf * SUPPLIERS_PER_SF))

    lines = rng.integers(1, 8, n_orders, dtype=np.int32)
    n = int(lines.sum())
    order_idx = np.repeat(np.arange(n_orders, dtype=np.int64), lines)
    first = np.cumsum(lines, dtype=np.int64) - lines
    linenumber = (np.arange(n, dtype=np.int64)
                  - np.repeat(first, lines) + 1).astype(np.int32)
    orderkey = ((order_idx // 8) * 32 + order_idx % 8 + 1).astype(np.int32)

    start, end = day_number("1992-01-01"), day_number("1998-08-02")
    current = day_number("1995-06-17")
    orderdate = rng.integers(start, end + 1, n_orders,
                             dtype=np.int32)[order_idx]
    shipdate = orderdate + rng.integers(1, 122, n, dtype=np.int32)
    commitdate = orderdate + rng.integers(30, 91, n, dtype=np.int32)
    receiptdate = shipdate + rng.integers(1, 31, n, dtype=np.int32)
    del orderdate, order_idx

    flag = codes["l_returnflag"]
    returned = receiptdate <= current
    rf_ra = np.where(rng.random(n) < 0.5, flag.index("R"), flag.index("A"))
    returnflag = np.where(returned, rf_ra, flag.index("N")).astype(np.int32)
    status = codes["l_linestatus"]
    linestatus = np.where(shipdate > current, status.index("O"),
                          status.index("F")).astype(np.int32)

    quantity = rng.integers(1, 51, n, dtype=np.int32)
    discount = rng.integers(0, 11, n, dtype=np.int32)
    tax = rng.integers(0, 9, n, dtype=np.int32)
    partkey = rng.integers(1, parts + 1, n, dtype=np.int32)
    suppkey = supplier_key(partkey, rng.integers(0, 4, n), suppliers)
    price_cents = np.rint(quantity * retail_price(partkey) * 100.0)

    cols = {
        "l_orderkey": orderkey,
        "l_partkey": partkey,
        "l_suppkey": suppkey,
        "l_linenumber": linenumber,
        "l_quantity": quantity.astype(np.float32),
        "l_extendedprice": (price_cents / 100.0).astype(np.float32),
        "l_discount": (discount / 100.0).astype(np.float32),
        "l_tax": (tax / 100.0).astype(np.float32),
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_shipdate": shipdate,
        "l_commitdate": commitdate,
        "l_receiptdate": receiptdate,
        "l_shipinstruct": rng.integers(
            0, len(codes["l_shipinstruct"]), n, dtype=np.int32),
        "l_shipmode": rng.integers(
            0, len(codes["l_shipmode"]), n, dtype=np.int32),
    }
    names: List[str] = [c["name"] for c in cfg["columns"]]
    missing = set(names) - set(cols)
    if missing:
        raise ValueError(f"no generator for columns {sorted(missing)}")
    return {name: cols[name] for name in names}
