"""Weights and corpus of the Mamba-2 training cell, made from the seed.

``init_weights`` makes every parameter on the device in one jitted call,
in float32 (the type the program trains in), in the benchmark's own
layout: ``embed`` (padded vocab, d), the per-layer stacks ``ln``,
``in_proj``, ``conv_w``, ``conv_b``, ``a_log``, ``dt_bias``, ``d_skip``,
``norm``, ``out_proj`` (leading axis: layer) and ``ln_f``.  The draws
follow ``mamba_ssm``'s initialisation: embeddings normal with standard
deviation 0.02; the projections and the depthwise convolution (weight
and bias) uniform within 1/sqrt(fan in), ``out_proj`` then divided by
sqrt(layers); A = -U[1, 16]; dt = exp(U[log 1e-3, log 1e-1]) stored as
its inverse softplus; D and the norm scales at one.

``to_program`` lays the same arrays out as the program's parameter tree
(layers scanned as one stack, or unrolled one by one) and
``program_names`` names each program leaf in this layout: ``embed``,
``ln_f``, a stacked ``<key>``, or ``<key>.<layer>`` for an unrolled one.

``make_corpus`` draws the token shards: ids below the tokenizer's
``vocab_size`` from a Zipf-Mandelbrot law (rank r has weight
1 / (r + 2.7)), ranks mapped to ids by a seeded permutation.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

LAYER_KEYS = ("ln", "in_proj", "conv_w", "conv_b", "a_log", "dt_bias",
              "d_skip", "norm", "out_proj")


def dims(cfg: Dict) -> Dict[str, int]:
    d, e = cfg["d_model"], cfg["expand"]
    di = e * d
    h = di // cfg["headdim"]
    gn = cfg["ngroups"] * cfg["d_state"]
    return {"d": d, "di": di, "h": h, "gn": gn, "cd": di + 2 * gn,
            "proj": 2 * di + 2 * gn + h, "L": cfg["n_layers"],
            "V": padded_vocab(cfg), "k": cfg["d_conv"]}


def padded_vocab(cfg: Dict) -> int:
    """Rows of the embedding: the vocabulary rounded up to a multiple of
    ``pad_vocab_size_multiple``."""
    k = int(cfg.get("pad_vocab_size_multiple", 1))
    return -(-int(cfg["vocab_size"]) // k) * k


def jax_key(seed: int):
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def init_weights(cfg: Dict, seed: int):
    import jax
    import jax.numpy as jnp
    m = dims(cfg)
    L = m["L"]

    def unif(key, shape, fan_in):
        b = fan_in ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -b, b)

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 7)
        dt = jnp.exp(jax.random.uniform(ks[4], (L, m["h"]), jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return {
            "embed": 0.02 * jax.random.normal(
                ks[0], (m["V"], m["d"]), jnp.float32),
            "ln": jnp.ones((L, m["d"]), jnp.float32),
            "in_proj": unif(ks[1], (L, m["d"], m["proj"]), m["d"]),
            "conv_w": unif(ks[2], (L, m["k"], m["cd"]), m["k"]),
            "conv_b": unif(ks[6], (L, m["cd"]), m["k"]),
            "a_log": jnp.log(jax.random.uniform(ks[3], (L, m["h"]),
                                                jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "d_skip": jnp.ones((L, m["h"]), jnp.float32),
            "norm": jnp.ones((L, m["di"]), jnp.float32),
            "out_proj": unif(ks[5], (L, m["di"], m["d"]), m["di"])
            * L ** -0.5,
            "ln_f": jnp.ones((m["d"],), jnp.float32),
        }

    return make(jax_key(seed))


def to_program(w: Dict, scan_layers: bool) -> Dict:
    """The program's tree (``repro.models.model.init_params``) holding
    these arrays, its layers scanned or unrolled."""
    import jax

    def block(p):
        return {"ln1": {"scale": p["ln"]},
                "mixer": {k: p[k] for k in LAYER_KEYS if k != "ln"}}

    @jax.jit
    def lay_out(w):
        stack = {k: w[k] for k in LAYER_KEYS}
        if scan_layers:
            layers = {"scan": [block(stack)]}
        else:
            layers = {"unrolled": [block({k: v[i] for k, v in stack.items()})
                                   for i in range(w["ln"].shape[0])]}
        return {"embed": w["embed"],
                "decoder": dict(prefix=[], extra=[], **layers),
                "ln_f": {"scale": w["ln_f"]}}

    return lay_out(w)


def program_names(tree) -> List[str]:
    """For each leaf of a program tree, in ``jax.tree.leaves`` order, the
    name of the same array in this layout."""
    import jax
    names = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        if keys[0] in ("embed", "ln_f"):
            names.append(keys[0])
            continue
        name = "ln" if keys[-2:] == ["ln1", "scale"] else keys[-1]
        names.append(f"{name}.{keys[2]}" if keys[1] == "unrolled" else name)
    return names


def leaf_of(arrays: Dict, name: str):
    """The array a name from ``program_names`` stands for: a whole
    array of this layout, or one layer of a stack."""
    key, _, layer = name.partition(".")
    return arrays[key][int(layer)] if layer else arrays[key]


def make_corpus(cfg: Dict, seed: int, shards: int, tokens: int
                ) -> List[np.ndarray]:
    V = int(cfg["vocab_size"])
    rng = np.random.default_rng([seed, 2])
    w = 1.0 / (np.arange(V) + 2.7)
    cdf = np.cumsum(w / w.sum())
    ids = rng.permutation(V).astype(np.int32)
    out = []
    for _ in range(shards):
        r = np.searchsorted(cdf, rng.random(tokens), side="right")
        out.append(ids[np.minimum(r, V - 1)])
    return out


class CorpusIndex:
    """Finds where a batch the loader delivered lies in the corpus, so
    the reference reads its tokens from the benchmark's own copy."""

    def __init__(self, shards: List[np.ndarray], gram: int = 8):
        self.shards, self.gram = shards, gram
        self.at: Dict[bytes, List] = {}
        for s, arr in enumerate(shards):
            for i in range(arr.size - gram + 1):
                self.at.setdefault(arr[i:i + gram].tobytes(), []).append(
                    (s, i))

    def locate(self, flat: np.ndarray) -> np.ndarray:
        """The corpus slice equal to ``flat``; raises if there is none."""
        for s, i in self.at.get(flat[:self.gram].tobytes(), ()):
            piece = self.shards[s][i:i + flat.size]
            if piece.size == flat.size and np.array_equal(piece, flat):
                return piece.copy()
        raise LookupError("batch is not a slice of the corpus")
