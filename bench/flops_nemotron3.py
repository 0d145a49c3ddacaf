"""Operations that the Nemotron 3 Nano training cell's work needs.

``train_flops_per_token``: forward and backward FLOPs of one token of
training on one chip's expert share, at sequence length ``seq``,
counting each multiply-add as two and the backward pass as twice the
forward, with no recompute.  Per layer, by its letter:

- M: the input and output projections, the depthwise convolution and
  the SSD dual form over chunks (as ``bench.flops`` counts it for
  Mamba-2);
- *: the q, k, v and o projections, and the scores and weighted sum over
  the causal half of the sequence;
- E: the router over every expert, the routed experts' up and down
  projections at ``k * E_l / E`` assignments a token (the useful work
  of the held experts, not their padded capacity), and the shared
  expert.

Then the output head over the vocabulary slice.  Elementwise work
(norms, gates, activations, softplus) is left out.
"""
from __future__ import annotations

from typing import Dict

from bench.gen.nemotron3 import dims


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    m = dims(cfg)
    d = m["d"]
    q, n, p = cfg["chunk_size"], cfg["ssm_state_size"], m["p"]
    pairs = (q + 1) / 2                      # causal half of a chunk
    mamba = (2 * d * m["proj"] + 2 * m["k"] * m["cd"]
             + 2 * pairs * n * cfg["n_groups"]
             + m["h"] * (2 * pairs * p + 4 * n * p)
             + 2 * m["di"] * d)
    attn = (2 * d * (m["H"] + 2 * m["KV"]) * m["hd"]
            + 2 * m["H"] * m["hd"] * d
            + 4 * m["H"] * m["hd"] * (seq + 1) / 2)
    share = cfg["num_experts_per_tok"] * m["El"] / m["E"]
    experts = (2 * d * m["E"] + share * 4 * d * m["f"] + 4 * d * m["fs"])
    per_kind = {"M": mamba, "*": attn, "E": experts}
    forward = sum(per_kind[c] for c in m["pattern"]) + 2 * d * m["V"]
    return 3.0 * forward
