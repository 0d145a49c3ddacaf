"""Multi-head Latent Attention (DeepSeek-V3).

Train/prefill: expand the compressed KV latent per head (standard form).
Decode: weight-absorbed form — queries are projected into the latent space so
attention runs against the (b, W, kv_lora_rank) compressed cache directly;
per-token cache cost is kv_lora_rank + qk_rope_head_dim instead of
n_heads * (qk_head_dim + v_head_dim)  (128x smaller for deepseek-v3).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.attention import (NEG_INF, attend_chunked, attend_dense,
                                    chunk_counts)
from repro.models.common import apply_rope, dense_init, shard_heads


def init_mla(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    d = cfg.d_model
    h = cfg.n_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = common.split_keys(key, 6)
    return {
        "wq_a": dense_init(ks[0], (d, qr), dtype=dtype),
        "q_norm": jnp.ones((qr,), dtype),
        "wq_b": dense_init(ks[1], (qr, h, dn + dr), dtype=dtype),
        "wkv_a": dense_init(ks[2], (d, kr + dr), dtype=dtype),
        "kv_norm": jnp.ones((kr,), dtype),
        "wkv_b": dense_init(ks[3], (kr, h, dn + dv), dtype=dtype),
        "wo": dense_init(ks[4], (h, dv, d), in_axis=1, dtype=dtype),
    }


def _scale(cfg: ModelConfig) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _queries(p: Dict, x: jax.Array, positions, cfg: ModelConfig):
    """-> q_nope (b,s,h,dn), q_pe (b,s,h,dr)."""
    q_lat = jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(x.dtype))
    q_lat = common.rms_norm(q_lat, p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q_lat, p["wq_b"].astype(x.dtype))
    q = shard_heads(q)
    q_nope = q[..., : cfg.qk_nope_head_dim]
    q_pe = apply_rope(q[..., cfg.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_pe


def _latent_kv(p: Dict, x: jax.Array, positions, cfg: ModelConfig):
    """-> c_kv (b,s,kr) normalised latent, k_pe (b,s,dr) shared rope key."""
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(x.dtype))
    c_kv = common.rms_norm(kv[..., : cfg.kv_lora_rank], p["kv_norm"],
                           cfg.norm_eps)
    k_pe = kv[..., cfg.kv_lora_rank:][:, :, None, :]     # (b,s,1,dr)
    k_pe = apply_rope(k_pe, positions, cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_pe


def mla_attention(p: Dict, x: jax.Array, positions: jax.Array,
                  cfg: ModelConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence causal MLA (expanded form). x: (b, s, d).
    -> (out, ``attention.chunk_counts``)."""
    b, s, _ = x.shape
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    q_nope, q_pe = _queries(p, x, positions, cfg)
    c_kv, k_pe = _latent_kv(p, x, positions, cfg)

    kv = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b"].astype(x.dtype))
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                  (*k_nope.shape[:3], cfg.qk_rope_head_dim))],
        axis=-1)

    pos = positions[0] if positions.ndim == 2 else positions
    mla_cfg_scale = _scale(cfg)
    # reuse the GQA machinery with a per-call scale override
    scfg = cfg.scaled(query_scale=mla_cfg_scale, attn_softcap=0.0)
    from repro.models.attention import _use_chunked
    counts = chunk_counts()
    if _use_chunked(s):
        out, counts = attend_chunked(q, k, v, pos, pos, scfg, causal=True,
                                     window=0)
    else:
        mask = pos[:, None] >= pos[None, :]
        out = attend_dense(q, k, v, mask, scfg)
    out = shard_heads(out)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype)), counts


# --------------------------------------------------------------------------
# Latent cache: prefill + absorbed decode
# --------------------------------------------------------------------------

def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Dict:
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "kpe": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
        "pos": jnp.full((max_len,), -1, jnp.int32),
    }


def mla_prefill(p: Dict, x: jax.Array, positions: jax.Array,
                cfg: ModelConfig, cache: Dict) -> Tuple[jax.Array, Dict]:
    out, _ = mla_attention(p, x, positions, cfg)
    c_kv, k_pe = _latent_kv(p, x, positions, cfg)
    pos = positions[0] if positions.ndim == 2 else positions
    s = x.shape[1]
    cache = {
        "ckv": jax.lax.dynamic_update_slice(
            cache["ckv"], c_kv.astype(cache["ckv"].dtype), (0, 0, 0)),
        "kpe": jax.lax.dynamic_update_slice(
            cache["kpe"], k_pe.astype(cache["kpe"].dtype), (0, 0, 0)),
        "pos": jax.lax.dynamic_update_slice(
            cache["pos"], pos.astype(jnp.int32), (0,)),
    }
    return out, cache


def mla_decode(p: Dict, x: jax.Array, position: jax.Array,
               cfg: ModelConfig, cache: Dict) -> Tuple[jax.Array, Dict]:
    """Absorbed single-token decode. x: (b, 1, d)."""
    b = x.shape[0]
    dn, dv, kr = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    posb = (jnp.zeros((1,), jnp.int32) + position)[None, :]

    q_nope, q_pe = _queries(p, x, posb, cfg)              # (b,1,h,*)
    c_new, kpe_new = _latent_kv(p, x, posb, cfg)          # (b,1,kr), (b,1,dr)

    slot = position  # latent cache is append-only (max_len slots)
    ckv = jax.lax.dynamic_update_slice(
        cache["ckv"], c_new.astype(cache["ckv"].dtype), (0, slot, 0))
    kpe = jax.lax.dynamic_update_slice(
        cache["kpe"], kpe_new.astype(cache["kpe"].dtype), (0, slot, 0))
    pos = jax.lax.dynamic_update_slice(
        cache["pos"], position[None].astype(jnp.int32), (slot,))

    wkv_b = p["wkv_b"].astype(x.dtype)                    # (kr, h, dn+dv)
    wk, wv = wkv_b[..., :dn], wkv_b[..., dn:]
    # absorb W_UK into the query:  (b,1,h,dn) x (kr,h,dn) -> (b,1,h,kr)
    q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, wk)

    ckv_t = ckv.astype(x.dtype)                           # (b, W, kr)
    kpe_t = kpe.astype(x.dtype)                           # (b, W, dr)
    logits = (jnp.einsum("bshr,bwr->bshw", q_abs, ckv_t) +
              jnp.einsum("bshk,bwk->bshw", q_pe, kpe_t))  # (b,1,h,W)
    logits = logits.astype(jnp.float32) * _scale(cfg)
    valid = (pos >= 0) & (pos <= position)                # (W,)
    logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)

    lat = jnp.einsum("bshw,bwr->bshr", probs, ckv_t)      # (b,1,h,kr)
    out = jnp.einsum("bshr,rhv->bshv", lat, wv)           # (b,1,h,dv)
    out = jnp.einsum("bshv,hvd->bsd", shard_heads(out), p["wo"].astype(x.dtype))
    return out, {"ckv": ckv, "kpe": kpe, "pos": pos}
