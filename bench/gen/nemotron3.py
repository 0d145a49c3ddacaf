"""Weights of the Nemotron 3 Nano training cell, made from the seed.

``init_weights`` makes every parameter on the device in one jitted call,
in float32 (the type the program trains in), in the benchmark's own
layout: ``embed`` (vocab slice, d), ``lm_head`` (d, vocab slice),
``ln_f``, and ``layers``, one dict a layer by its letter in the pattern:

- M (Mamba-2): ``ln``, ``in_proj`` (d, 2*di + 2*g*n + h), ``conv_w``
  (k, di + 2*g*n), ``conv_b``, ``a_log``, ``dt_bias``, ``d_skip`` (h),
  ``norm`` (di), ``out_proj`` (di, d);
- * (attention): ``ln``, ``wq`` (d, H, hd), ``wk``, ``wv`` (d, KV, hd),
  ``wo`` (H, hd, d);
- E (experts): ``ln``, ``router`` (d, E) over every expert,
  ``router_bias`` (E), the held experts' ``w_up`` (E_l, d, f) and
  ``w_down`` (E_l, f, d), the shared expert's ``ws_up`` and ``ws_down``.

The draws follow HF ``modeling_nemotron_h``'s ``_init_weights``: linear
layers and embeddings normal with standard deviation 0.02
(``initializer_range``), biases zero; the depthwise convolution torch's
default, uniform within 1/sqrt(kernel); ``out_proj`` uniform within
1/sqrt(di) then divided by sqrt(published layers)
(``rescale_prenorm_residual``); A = arange(1, h + 1); dt =
exp(U[log time_step_min, log time_step_max]) at least time_step_floor,
stored as its inverse softplus; D, norms at one; the router's correction
bias at zero.

``to_program`` lays the arrays out as the program's parameter tree (the
layers unrolled) and ``program_names`` names each program leaf in this
layout: ``embed``, ``lm_head``, ``ln_f`` or ``<key>.<layer>``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.gen.mamba2 import jax_key

MAMBA_KEYS = ("in_proj", "conv_w", "conv_b", "a_log", "dt_bias", "d_skip",
              "norm", "out_proj")
ATTN_KEYS = ("wq", "wk", "wv", "wo")
MOE_KEYS = ("router", "router_bias", "w_up", "w_down", "ws_up", "ws_down")
KEYS = {"M": MAMBA_KEYS, "*": ATTN_KEYS, "E": MOE_KEYS}


def dims(cfg: Dict) -> Dict:
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    di, gn = h * p, cfg["n_groups"] * cfg["ssm_state_size"]
    dep = cfg["deployment"]
    return {"d": cfg["hidden_size"], "di": di, "h": h, "p": p, "gn": gn,
            "cd": di + 2 * gn, "proj": 2 * di + 2 * gn + h,
            "k": cfg["conv_kernel"], "H": cfg["num_attention_heads"],
            "KV": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "E": dep["router_experts"], "El": cfg["n_routed_experts"],
            "off": dep["expert_offset"], "f": cfg["moe_intermediate_size"],
            "fs": cfg["moe_shared_expert_intermediate_size"],
            "V": cfg["vocab_size"], "pattern": cfg["hybrid_override_pattern"]}


def init_weights(cfg: Dict, seed: int):
    import jax
    import jax.numpy as jnp
    m = dims(cfg)
    d, std = m["d"], 0.02
    depth = cfg["deployment"]["published_layers"]

    def normal(key, shape):
        return std * jax.random.normal(key, shape, jnp.float32)

    def unif(key, shape, bound):
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)

    def mamba(key):
        ks = jax.random.split(key, 5)
        lo = np.log(cfg["time_step_min"])
        hi = np.log(cfg["time_step_max"])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            ks[3], (m["h"],), jnp.float32, lo, hi)), cfg["time_step_floor"])
        return {
            "ln": jnp.ones((d,), jnp.float32),
            "in_proj": normal(ks[0], (d, m["proj"])),
            "conv_w": unif(ks[1], (m["k"], m["cd"]), m["k"] ** -0.5),
            "conv_b": unif(ks[2], (m["cd"],), m["k"] ** -0.5),
            "a_log": jnp.log(jnp.arange(1, m["h"] + 1, dtype=jnp.float32)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "d_skip": jnp.ones((m["h"],), jnp.float32),
            "norm": jnp.ones((m["di"],), jnp.float32),
            "out_proj": unif(ks[4], (m["di"], d), m["di"] ** -0.5)
            * depth ** -0.5,
        }

    def attention(key):
        ks = jax.random.split(key, 4)
        return {"ln": jnp.ones((d,), jnp.float32),
                "wq": normal(ks[0], (d, m["H"], m["hd"])),
                "wk": normal(ks[1], (d, m["KV"], m["hd"])),
                "wv": normal(ks[2], (d, m["KV"], m["hd"])),
                "wo": normal(ks[3], (m["H"], m["hd"], d))}

    def experts(key):
        ks = jax.random.split(key, 5)
        return {"ln": jnp.ones((d,), jnp.float32),
                "router": normal(ks[0], (d, m["E"])),
                "router_bias": jnp.zeros((m["E"],), jnp.float32),
                "w_up": normal(ks[1], (m["El"], d, m["f"])),
                "w_down": normal(ks[2], (m["El"], m["f"], d)),
                "ws_up": normal(ks[3], (d, m["fs"])),
                "ws_down": normal(ks[4], (m["fs"], d))}

    make_layer = {"M": mamba, "*": attention, "E": experts}

    @jax.jit
    def make(key):
        ks = jax.random.split(key, len(m["pattern"]) + 2)
        return {"embed": normal(ks[0], (m["V"], d)),
                "lm_head": normal(ks[1], (d, m["V"])),
                "ln_f": jnp.ones((d,), jnp.float32),
                "layers": [make_layer[c](ks[i + 2])
                           for i, c in enumerate(m["pattern"])]}

    return make(jax_key(seed))


def to_program(w: Dict, pattern: str) -> Dict:
    """The program's tree (``repro.models.model.init_params`` with the
    layers unrolled) holding these arrays."""
    import jax

    def block(c, p):
        out = {"ln1": {"scale": p["ln"]}}
        part = "moe" if c == "E" else "mixer"
        out[part] = {k: p[k] for k in KEYS[c]}
        return out

    @jax.jit
    def lay_out(w):
        return {"embed": w["embed"], "lm_head": w["lm_head"],
                "decoder": {"prefix": [], "extra": [],
                            "unrolled": [block(c, p) for c, p in
                                         zip(pattern, w["layers"])]},
                "ln_f": {"scale": w["ln_f"]}}

    return lay_out(w)


def program_names(tree) -> List[str]:
    """For each leaf of a program tree, in ``jax.tree.leaves`` order, the
    name of the same array in this layout."""
    import jax
    names = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        if keys[0] in ("embed", "lm_head", "ln_f"):
            names.append(keys[0])
            continue
        name = "ln" if keys[-2:] == ["ln1", "scale"] else keys[-1]
        names.append(f"{name}.{keys[2]}")
    return names


def leaf_of(w: Dict, name: str):
    """The array a name from ``program_names`` stands for."""
    key, _, layer = name.partition(".")
    return w["layers"][int(layer)][key] if layer else w[key]
