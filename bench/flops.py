"""Operations and bytes that a cell's work needs, from its shapes.

``mamba2_train_flops_per_token``: forward and backward FLOPs of one
token of Mamba-2 language-model training, counting each multiply-add
as two and the backward pass as twice the forward, with no recompute:
per layer the input and output projections, the depthwise convolution
and the SSD dual form over chunks of length Q (the C.B scores of the
causal half of a chunk per group; per head the weighted sum over the
chunk, the chunk state and the state read-out), and the tied output
head.  Elementwise work (norms, gates, softplus) is left out.

``bench.drivers.query.logical_cost`` counts a query's bytes and
operations the same way for the scans.
"""
from __future__ import annotations

from typing import Dict

from bench.gen.mamba2 import dims


def mamba2_train_flops_per_token(cfg: Dict) -> float:
    m = dims(cfg)
    q, n, p = cfg["chunk_size"], cfg["d_state"], cfg["headdim"]
    pairs = (q + 1) / 2                      # causal half of a chunk
    per_layer = (2 * m["d"] * m["proj"]                     # in_proj
                 + 2 * m["k"] * m["cd"]                     # conv
                 + 2 * pairs * n * cfg["ngroups"]           # C.B scores
                 + m["h"] * (2 * pairs * p + 4 * n * p)     # y, state, out
                 + 2 * m["di"] * m["d"])                    # out_proj
    forward = m["L"] * per_layer + 2 * m["d"] * m["V"]      # tied head
    return 3.0 * forward
