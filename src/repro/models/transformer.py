"""Composable decoder / encoder-decoder stacks over the block zoo.

Layers are grouped into repetitions of the architecture's ``attn_pattern``
and scanned with ``jax.lax.scan`` over stacked parameters (bounded compile
time for 46-100-layer configs); layers that don't fill a whole pattern
period are run unrolled ("extra" layers).  Each block kind (global / local /
cross / rglru / ssd / encoder / encdec) exposes train, prefill and decode
paths with a per-layer cache pytree.  Nemotron-H's hybrid stacks add two
kinds that are whole blocks on their own: an expert layer (``moe``) and
attention with no MLP (``attn``).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN_ONLY, CROSS_ATTN, GLOBAL_ATTN,
                                LOCAL_ATTN, MOE, RGLRU, SSD, ModelConfig)
from repro.models import attention as attn
from repro.models import common, mla, moe, rglru, ssm
from repro.models.common import dense_init, shard_batch_seq, shard_ff

# internal block kinds beyond the config pattern
ENCODER = "encoder"          # bidirectional self attention (whisper encoder)
ENCDEC = "encdec"            # self + cross attention (whisper decoder)

# kinds whose block is its mixer alone, with no MLP after it
MIXER_ONLY = (SSD, ATTN_ONLY)


def no_aux() -> Dict[str, jax.Array]:
    """A block's auxiliary outputs where it has no expert layer: the
    load-balance loss and the expert layer's counters, all zero."""
    zero = jnp.zeros((), jnp.int32)
    return {"balance": jnp.zeros((), jnp.float32), "moe_assigned": zero,
            "moe_dropped": zero}


def add_aux(a: Dict, b: Dict) -> Dict:
    return jax.tree.map(jnp.add, a, b)


def _uses_layernorm(cfg: ModelConfig) -> bool:
    return cfg.family == "audio"


# --------------------------------------------------------------------------
# Norm / MLP
# --------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    p = {"scale": (jnp.zeros if cfg.sandwich_norm else jnp.ones)((cfg.d_model,), dtype)}
    if _uses_layernorm(cfg):
        p = {"scale": jnp.ones((cfg.d_model,), dtype),
             "bias": jnp.zeros((cfg.d_model,), dtype)}
    return p


def apply_norm(p: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if "bias" in p:
        return common.layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
    return common.rms_norm(x, p["scale"], cfg.norm_eps,
                           zero_centered=cfg.sandwich_norm)


def init_mlp(key, cfg: ModelConfig, d_ff: int, dtype=jnp.float32) -> Dict:
    d = cfg.d_model
    ks = common.split_keys(key, 3)
    if _uses_layernorm(cfg):      # whisper: plain GELU MLP with biases
        return {
            "wi": dense_init(ks[0], (d, d_ff), dtype=dtype),
            "bi": jnp.zeros((d_ff,), dtype),
            "wo": dense_init(ks[1], (d_ff, d), dtype=dtype),
            "bo": jnp.zeros((d,), dtype),
        }
    return {
        "wi_gate": dense_init(ks[0], (d, d_ff), dtype=dtype),
        "wi_up": dense_init(ks[1], (d, d_ff), dtype=dtype),
        "wo": dense_init(ks[2], (d_ff, d), dtype=dtype),
    }


def mlp_forward(p: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    act = common.activation(cfg.act)
    if "wi" in p:
        h = act(jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype))
                + p["bi"].astype(x.dtype))
        h = shard_ff(h)
        return jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype)) \
            + p["bo"].astype(x.dtype)
    h = act(jnp.einsum("bsd,df->bsf", x, p["wi_gate"].astype(x.dtype))) * \
        jnp.einsum("bsd,df->bsf", x, p["wi_up"].astype(x.dtype))
    h = shard_ff(h)
    return jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype))


# --------------------------------------------------------------------------
# Block init
# --------------------------------------------------------------------------

def _layer_dff(cfg: ModelConfig, layer_idx: int) -> Tuple[bool, int]:
    """-> (is_moe_layer, d_ff) for decoder layer `layer_idx`."""
    if cfg.is_moe and layer_idx >= cfg.n_dense_layers:
        return True, 0
    if cfg.is_moe:
        return False, cfg.dense_d_ff or cfg.d_ff
    return False, cfg.d_ff


def init_block(key, cfg: ModelConfig, kind: str, layer_idx: int,
               dtype=jnp.float32) -> Dict:
    ks = common.split_keys(key, 6)
    p: Dict[str, Any] = {"ln1": init_norm(cfg, dtype)}

    if kind == MOE:      # nemotron-h 'E': the expert layer is the block
        p["moe"] = moe.init_moe(ks[2], cfg, dtype)
        return p
    if kind in (GLOBAL_ATTN, LOCAL_ATTN, ATTN_ONLY, ENCODER, ENCDEC):
        if cfg.use_mla:
            p["mixer"] = mla.init_mla(ks[0], cfg, dtype)
        else:
            p["mixer"] = attn.init_attention(ks[0], cfg, dtype=dtype)
    elif kind == CROSS_ATTN:
        p["mixer"] = attn.init_attention(ks[0], cfg, cross=True, dtype=dtype)
        p["mlp_gate"] = jnp.zeros((), dtype)
    elif kind == RGLRU:
        p["mixer"] = rglru.init_rglru(ks[0], cfg, dtype)
    elif kind == SSD:
        p["mixer"] = ssm.init_ssm(ks[0], cfg, dtype)
    else:
        raise ValueError(f"unknown block kind {kind!r}")

    if kind == ENCDEC:   # whisper decoder: extra cross-attn sub-block
        p["ln_cross"] = init_norm(cfg, dtype)
        p["cross"] = attn.init_attention(ks[1], cfg, dtype=dtype)

    if kind not in MIXER_ONLY:
        p["ln2"] = init_norm(cfg, dtype)
        is_moe, dff = _layer_dff(cfg, layer_idx)
        if is_moe:
            p["moe"] = moe.init_moe(ks[2], cfg, dtype)
        else:
            p["mlp"] = init_mlp(ks[2], cfg, dff, dtype)

    if cfg.sandwich_norm:
        p["ln1_post"] = init_norm(cfg, dtype)
        if "ln2" in p:
            p["ln2_post"] = init_norm(cfg, dtype)
    return p


# --------------------------------------------------------------------------
# Block forward — train/prefill/decode
# --------------------------------------------------------------------------

def block_forward(p: Dict, x: jax.Array, cfg: ModelConfig, kind: str, *,
                  mode: str, positions: Optional[jax.Array] = None,
                  position: Optional[jax.Array] = None,
                  cache: Optional[Dict] = None,
                  memory: Optional[jax.Array] = None,
                  moe_dense_oracle: bool = False,
                  ) -> Tuple[jax.Array, Dict, Optional[Dict]]:
    """One block. Returns (x, aux, new_cache): aux as ``no_aux`` gives it."""
    aux = no_aux()
    new_cache = cache
    h = apply_norm(p["ln1"], x, cfg)
    window = cfg.local_window if kind == LOCAL_ATTN else 0

    if kind == MOE:
        y, aux = _moe(p["moe"], h, cfg, moe_dense_oracle)
        return shard_batch_seq(x + y), aux, new_cache

    # ---- sequence mixer ----
    if kind in (GLOBAL_ATTN, LOCAL_ATTN, ATTN_ONLY):
        if cfg.use_mla:
            if mode == "train":
                mix = mla.mla_attention(p["mixer"], h, positions, cfg)
            elif mode == "prefill":
                mix, new_cache = mla.mla_prefill(p["mixer"], h, positions,
                                                 cfg, cache)
            else:
                mix, new_cache = mla.mla_decode(p["mixer"], h, position,
                                                cfg, cache)
        else:
            if mode == "train":
                mix = attn.self_attention(p["mixer"], h, positions, cfg,
                                          window=window)
            elif mode == "prefill":
                mix, new_cache = attn.prefill_attention(
                    p["mixer"], h, positions, cfg, cache, window=window)
            else:
                mix, new_cache = attn.decode_attention(
                    p["mixer"], h, position, cfg, cache, window=window)
    elif kind == ENCODER:
        # bidirectional: dense path with an all-true mask
        q, k, v = attn._project_qkv(p["mixer"], h, cfg)
        mask = jnp.ones((h.shape[1], h.shape[1]), bool)
        mix = attn.attend_dense(q, k, v, mask, cfg)
        mix = jnp.einsum("bshk,hkd->bsd", mix, p["mixer"]["wo"].astype(x.dtype))
    elif kind == ENCDEC:
        if mode == "train":
            mix = attn.self_attention(p["mixer"], h, positions, cfg,
                                      use_rope=False)
        elif mode == "prefill":
            sub = {k: cache[k] for k in ("k", "v", "pos")}
            mix, new_cache = attn.prefill_attention(p["mixer"], h, positions,
                                                    cfg, sub)
        else:
            sub = {k: cache[k] for k in ("k", "v", "pos")}
            mix, new_cache = attn.decode_attention(p["mixer"], h, position,
                                                   cfg, sub)
            # carry the (static) cross-attn K/V forward
            new_cache = dict(new_cache)
            new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
    elif kind == CROSS_ATTN:
        kv_override = None
        if mode == "decode" and cache is not None and "xk" in cache:
            kv_override = (cache["xk"].astype(x.dtype),
                           cache["xv"].astype(x.dtype))
        mix = attn.cross_attention(p["mixer"], h, memory, cfg, gated=True,
                                   kv_override=kv_override)
        if mode == "prefill":
            xk, xv = attn.cross_kv(p["mixer"], memory, cfg, x.dtype)
            new_cache = {"xk": xk, "xv": xv}
    elif kind == RGLRU:
        if mode == "train":
            mix, _, _ = rglru.rglru_block(p["mixer"], h, cfg)
        elif mode == "prefill":
            mix, new_cache = rglru.rglru_prefill(p["mixer"], h, cfg, cache)
        else:
            mix, new_cache = rglru.rglru_decode(p["mixer"], h, cfg, cache)
    elif kind == SSD:
        if mode == "train":
            mix = ssm.ssm_block(p["mixer"], h, cfg)
        elif mode == "prefill":
            mix, new_cache = ssm.ssm_prefill(p["mixer"], h, cfg, cache)
        else:
            mix, new_cache = ssm.ssm_decode(p["mixer"], h, cfg, cache)
    else:
        raise ValueError(kind)

    if cfg.sandwich_norm:
        mix = apply_norm(p["ln1_post"], mix, cfg)
    x = shard_batch_seq(x + mix)

    # ---- whisper decoder cross-attention sub-block ----
    if kind == ENCDEC:
        hc = apply_norm(p["ln_cross"], x, cfg)
        kv_override = None
        if mode == "decode" and cache is not None and "xk" in (cache or {}):
            kv_override = (cache["xk"].astype(x.dtype),
                           cache["xv"].astype(x.dtype))
        cx = attn.cross_attention(p["cross"], hc, memory, cfg,
                                  kv_override=kv_override)
        x = x + cx
        if mode == "prefill":
            xk, xv = attn.cross_kv(p["cross"], memory, cfg, x.dtype)
            new_cache = dict(new_cache or {})
            new_cache.update({"xk": xk, "xv": xv})

    # ---- MLP / MoE ----
    if kind not in MIXER_ONLY:
        h2 = apply_norm(p["ln2"], x, cfg)
        if "moe" in p:
            y, aux = _moe(p["moe"], h2, cfg, moe_dense_oracle)
        else:
            y = mlp_forward(p["mlp"], h2, cfg)
        if kind == CROSS_ATTN:
            y = jnp.tanh(p["mlp_gate"].astype(x.dtype)) * y
        if cfg.sandwich_norm:
            y = apply_norm(p["ln2_post"], y, cfg)
        x = shard_batch_seq(x + y)
    return x, aux, new_cache


def _moe(p: Dict, h: jax.Array, cfg: ModelConfig, dense_oracle: bool):
    fn = moe.moe_block_dense if dense_oracle else moe.moe_block
    return fn(p, h, cfg)


# --------------------------------------------------------------------------
# Cache init per kind
# --------------------------------------------------------------------------

def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype=jnp.bfloat16) -> Optional[Dict]:
    if kind == MOE:
        return None
    if kind in (GLOBAL_ATTN, ATTN_ONLY, ENCDEC):
        if cfg.use_mla:
            return mla.init_mla_cache(cfg, batch, max_len, dtype)
        c = attn.init_cache(cfg, batch, max_len, "global", dtype)
        if kind == ENCDEC:
            hd = cfg.head_dim
            c["xk"] = jnp.zeros((batch, cfg.encoder_seq, cfg.n_kv_heads, hd), dtype)
            c["xv"] = jnp.zeros((batch, cfg.encoder_seq, cfg.n_kv_heads, hd), dtype)
        return c
    if kind == LOCAL_ATTN:
        return attn.init_cache(cfg, batch, max_len, "local", dtype)
    if kind == CROSS_ATTN:
        # filled at prefill with image K/V; placeholder zeros here
        hd = cfg.head_dim
        return {"xk": jnp.zeros((batch, cfg.n_image_tokens, cfg.n_kv_heads, hd), dtype),
                "xv": jnp.zeros((batch, cfg.n_image_tokens, cfg.n_kv_heads, hd), dtype)}
    if kind == RGLRU:
        return rglru.init_rglru_cache(cfg, batch)
    if kind == SSD:
        return ssm.init_ssm_cache(cfg, batch)
    raise ValueError(kind)


# --------------------------------------------------------------------------
# Stack: pattern grouping
# --------------------------------------------------------------------------

def stack_plan(cfg: ModelConfig) -> Tuple[int, int, Tuple[str, ...], Tuple[str, ...]]:
    """-> (prefix, reps, pattern, extra_kinds).

    n_layers = prefix + reps*|pattern| + |extras|.  ``prefix`` layers are
    run unrolled (deepseek's first-k dense layers have a different param
    structure from the MoE layers so they cannot share the scan stack).
    """
    pattern = cfg.attn_pattern
    period = len(pattern)
    prefix = cfg.n_dense_layers if cfg.is_moe else 0
    body = cfg.n_layers - prefix
    reps = body // period
    extra = tuple(pattern[i % period] for i in range(reps * period, body))
    return prefix, reps, pattern, extra


def init_stack(key, cfg: ModelConfig, dtype=jnp.float32,
               scan_layers: bool = True) -> Dict:
    """Stacked (scan-ready) decoder blocks + prefix/extras."""
    prefix, reps, pattern, extra = stack_plan(cfg)
    out: Dict[str, Any] = {}
    keys = common.split_keys(key, cfg.n_layers + 1)
    ki = 0

    out["prefix"] = []
    for i in range(prefix):
        out["prefix"].append(
            init_block(keys[ki], cfg, pattern[i % len(pattern)], i, dtype))
        ki += 1

    if scan_layers and reps > 1:
        stacked = []
        for pos, kind in enumerate(pattern):
            per_rep = []
            for r in range(reps):
                layer_idx = prefix + r * len(pattern) + pos
                per_rep.append(init_block(keys[ki], cfg, kind, layer_idx, dtype))
                ki += 1
            stacked.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_rep))
        out["scan"] = stacked
    else:
        blocks = []
        for i in range(reps * len(pattern)):
            blocks.append(init_block(keys[ki], cfg, pattern[i % len(pattern)],
                                     prefix + i, dtype))
            ki += 1
        out["unrolled"] = blocks

    extras = []
    base = prefix + reps * len(pattern)
    for j, kind in enumerate(extra):
        extras.append(init_block(keys[ki], cfg, kind, base + j, dtype))
        ki += 1
    out["extra"] = extras
    return out


def _remat_policy(name: str):
    if name == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    if name == "full":
        return None
    return jax.checkpoint_policies.everything_saveable


def stack_forward_train(stack: Dict, x: jax.Array, cfg: ModelConfig, *,
                        positions: jax.Array, memory=None,
                        remat: str = "none",
                        moe_dense_oracle: bool = False) -> Tuple[jax.Array, Dict]:
    """Full-sequence forward through all decoder blocks -> (x, aux summed
    over the blocks)."""
    prefix, reps, pattern, extra = stack_plan(cfg)
    aux_total = no_aux()

    for i, bp in enumerate(stack["prefix"]):
        x, aux, _ = block_forward(bp, x, cfg, pattern[i % len(pattern)],
                                  mode="train", positions=positions,
                                  memory=memory,
                                  moe_dense_oracle=moe_dense_oracle)
        aux_total = add_aux(aux_total, aux)

    def one_rep(x, layer_params):
        aux_sum = no_aux()
        for pos, kind in enumerate(pattern):
            x, aux, _ = block_forward(
                layer_params[pos], x, cfg, kind, mode="train",
                positions=positions, memory=memory,
                moe_dense_oracle=moe_dense_oracle)
            aux_sum = add_aux(aux_sum, aux)
        return x, aux_sum

    if "scan" in stack:
        fn = one_rep
        if remat != "none":
            fn = jax.checkpoint(one_rep, policy=_remat_policy(remat),
                                prevent_cse=False)
        x, auxs = jax.lax.scan(lambda c, p: fn(c, p), x, tuple(stack["scan"]))
        aux_total = add_aux(aux_total, jax.tree.map(jnp.sum, auxs))
    else:
        for i, bp in enumerate(stack["unrolled"]):
            block = partial(block_forward, cfg=cfg,
                            kind=pattern[i % len(pattern)], mode="train",
                            positions=positions, memory=memory,
                            moe_dense_oracle=moe_dense_oracle)
            if remat != "none":
                block = jax.checkpoint(block, policy=_remat_policy(remat))
            x, aux, _ = block(bp, x)
            aux_total = add_aux(aux_total, aux)

    for j, bp in enumerate(stack["extra"]):
        x, aux, _ = block_forward(bp, x, cfg, extra[j], mode="train",
                                  positions=positions, memory=memory,
                                  moe_dense_oracle=moe_dense_oracle)
        aux_total = add_aux(aux_total, aux)
    return x, aux_total


def init_stack_cache(cfg: ModelConfig, batch: int, max_len: int,
                     scan_layers: bool = True, dtype=jnp.bfloat16) -> Dict:
    prefix, reps, pattern, extra = stack_plan(cfg)
    out: Dict[str, Any] = {}
    out["prefix"] = [
        init_block_cache(cfg, pattern[i % len(pattern)], batch, max_len, dtype)
        for i in range(prefix)
    ]
    if scan_layers and reps > 1:
        out["scan"] = [
            jax.tree.map(lambda x: jnp.stack([x] * reps),
                         init_block_cache(cfg, kind, batch, max_len, dtype))
            for kind in pattern
        ]
    else:
        out["unrolled"] = [
            init_block_cache(cfg, pattern[i % len(pattern)], batch, max_len, dtype)
            for i in range(reps * len(pattern))
        ]
    out["extra"] = [init_block_cache(cfg, kind, batch, max_len, dtype)
                    for kind in extra]
    return out


def _stack_step(stack: Dict, caches: Dict, x: jax.Array, cfg: ModelConfig, *,
                mode: str, positions=None, position=None, memory=None
                ) -> Tuple[jax.Array, Dict]:
    """Shared prefill/decode walk over the stack, threading caches."""
    prefix, reps, pattern, extra = stack_plan(cfg)
    new_caches: Dict[str, Any] = {}

    new_prefix = []
    for i, bp in enumerate(stack["prefix"]):
        x, _, nc = block_forward(bp, x, cfg, pattern[i % len(pattern)],
                                 mode=mode, positions=positions,
                                 position=position,
                                 cache=caches["prefix"][i], memory=memory)
        new_prefix.append(nc)
    new_caches["prefix"] = new_prefix

    if "scan" in stack:
        def one_rep(x, inputs):
            layer_params, layer_cache = inputs
            new_lc = []
            for pos, kind in enumerate(pattern):
                x, _, nc = block_forward(
                    layer_params[pos], x, cfg, kind, mode=mode,
                    positions=positions, position=position,
                    cache=layer_cache[pos], memory=memory)
                new_lc.append(nc)
            return x, tuple(new_lc)

        x, new_sc = jax.lax.scan(one_rep, x,
                                 (tuple(stack["scan"]), tuple(caches["scan"])))
        new_caches["scan"] = list(new_sc)
    else:
        new_list = []
        for i, bp in enumerate(stack["unrolled"]):
            kind = pattern[i % len(pattern)]
            x, _, nc = block_forward(bp, x, cfg, kind, mode=mode,
                                     positions=positions, position=position,
                                     cache=caches["unrolled"][i], memory=memory)
            new_list.append(nc)
        new_caches["unrolled"] = new_list

    new_extra = []
    for j, bp in enumerate(stack["extra"]):
        x, _, nc = block_forward(bp, x, cfg, extra[j], mode=mode,
                                 positions=positions, position=position,
                                 cache=caches["extra"][j], memory=memory)
        new_extra.append(nc)
    new_caches["extra"] = new_extra
    return x, new_caches


def stack_forward_prefill(stack, caches, x, cfg, *, positions, memory=None):
    return _stack_step(stack, caches, x, cfg, mode="prefill",
                       positions=positions, memory=memory)


def stack_forward_decode(stack, caches, x, cfg, *, position, memory=None):
    return _stack_step(stack, caches, x, cfg, mode="decode",
                       position=position, memory=memory)


# --------------------------------------------------------------------------
# Positional embeddings (whisper)
# --------------------------------------------------------------------------

def sinusoid_positions(length: int, d_model: int, dtype=jnp.float32) -> jax.Array:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d_model // 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-math.log(10000.0) * dim / max(d_model // 2 - 1, 1))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)
