"""Peak rates of one chip by JAX's ``device_kind``, from ``peaks.json``
(which names its source).  A kind that is not in the table is an error,
never a default."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

TABLE = Path(__file__).resolve().parent / "peaks.json"


def load(kind: str, table: Path = TABLE) -> Dict[str, float]:
    with open(table) as f:
        kinds = json.load(f)["kinds"]
    if kind not in kinds:
        raise KeyError(f"no peak rates for device kind {kind!r} in {table}")
    return {k: float(v) for k, v in kinds[kind].items()}


def least_seconds(nbytes: float, nops: float, peak: Dict[str, float]
                  ) -> float:
    """The least time a piece of work needs on a chip of ``peak``: its
    bytes over the HBM bandwidth or its operations over the bf16 peak,
    whichever is longer."""
    return max(nbytes / peak["hbm_bytes_per_s"],
               nops / peak["bf16_flops_per_s"])
