"""Grouped-query attention: global / sliding-window / cross, with KV caches.

Three execution paths with identical semantics:
  * ``attend_dense``    — materialised scores; smoke tests & short sequences.
  * ``attend_chunked``  — XLA online-softmax over KV chunks; long sequences
                          (bounded memory, same FLOPs — the portable
                          "flash attention in XLA" used by the dry-run).
  * Pallas flash kernel — ``repro.kernels.ops.flash_attention`` on TPU.

Caches are fixed-size ring buffers: ``k/v`` of length ``W`` plus a ``pos``
vector holding the absolute position stored in each slot (-1 = empty).  For
global attention W = max_len; for sliding-window layers W = window, which is
what makes recurrentgemma's 500k decode O(window) instead of O(seq).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.common import apply_rope, dense_init, shard_heads, softcap

NEG_INF = -2.0e38  # fp32-safe mask value


# --------------------------------------------------------------------------
# Params
# --------------------------------------------------------------------------

def head_maps(cfg: ModelConfig):
    """TP head-padding maps: (q_slot -> real q idx or -1, kv_slot -> real kv).

    See configs.base.apply_tp_padding: padded q slots are laid out so that
    slot j's padded KV group (j // (n_heads/n_kv)) replicates the original
    head's real KV group — function-preserving GQA KV replication.
    """
    h, kv = cfg.n_heads, cfg.n_kv_heads
    hr, kvr = cfg.n_heads_real, cfg.n_kv_heads_real
    if h == hr and kv == kvr:
        return list(range(h)), list(range(kv))
    if kvr == kv:
        # only q was padded (MoE-style trailing pad)
        return [i if i < hr else -1 for i in range(h)], list(range(kv))
    if kvr == hr:
        # MHA joint pad: identity prefix
        qmap = [i if i < hr else -1 for i in range(h)]
        kvmap = [i if i < kvr else 0 for i in range(kv)]
        return qmap, kvmap
    rep = kv // kvr                       # kv replication factor
    g_real = hr // kvr                    # real q heads per kv group
    slots_per_kv_group = h // kvr         # = rep * padded group
    qmap = [-1] * h
    for k in range(kvr):
        for i0 in range(g_real):
            qmap[k * slots_per_kv_group + i0] = k * g_real + i0
    kvmap = [c // rep for c in range(kv)]
    return qmap, kvmap


def _place_heads(w_real: jax.Array, qmap, axis: int) -> jax.Array:
    """Scatter real head slices into the padded layout (zeros elsewhere)."""
    parts = []
    for j in qmap:
        if j < 0:
            parts.append(jnp.zeros_like(jnp.take(w_real, 0, axis=axis)))
        else:
            parts.append(jnp.take(w_real, j, axis=axis))
    return jnp.stack(parts, axis=axis)


def init_attention(key, cfg: ModelConfig, *, cross: bool = False,
                   dtype=jnp.float32) -> Dict:
    """QKVO projections (+ optional biases, cross-attn gate/norms)."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hr, kvr = cfg.n_heads_real, cfg.n_kv_heads_real
    ks = common.split_keys(key, 8)
    wq = dense_init(ks[0], (d, hr, hd), dtype=dtype)
    wk = dense_init(ks[1], (d, kvr, hd), dtype=dtype)
    wv = dense_init(ks[2], (d, kvr, hd), dtype=dtype)
    wo = dense_init(ks[3], (hr, hd, d), in_axis=1, dtype=dtype)
    if (h, kv) != (hr, kvr):
        qmap, kvmap = head_maps(cfg)
        wq = _place_heads(wq, qmap, axis=1)
        wo = _place_heads(wo, qmap, axis=0)
        wk = _place_heads(wk, kvmap, axis=1)
        wv = _place_heads(wv, kvmap, axis=1)
    p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((kv, hd), dtype)
        p["bv"] = jnp.zeros((kv, hd), dtype)
    if cross:
        # llama-3.2-vision style gated cross attention: rmsnorm on q/k,
        # tanh gates on attn output (the MLP gate lives in the block).
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
        p["gate"] = jnp.zeros((), dtype)
    return p


# --------------------------------------------------------------------------
# Core attention math
# --------------------------------------------------------------------------

def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(b, s, kv, hd) -> (b, s, h, hd) by repeating each kv group."""
    b, s, kv, hd = k.shape
    if kv == n_heads:
        return k
    rep = n_heads // kv
    return jnp.repeat(k, rep, axis=2)


def _scale(cfg: ModelConfig) -> float:
    if cfg.query_scale is not None:
        return cfg.query_scale
    return 1.0 / math.sqrt(cfg.head_dim)


def attend_dense(q: jax.Array, k: jax.Array, v: jax.Array,
                 mask: jax.Array, cfg: ModelConfig) -> jax.Array:
    """q: (b, sq, h, hd); k/v: (b, sk, kv, hd); mask: (b?, sq, sk) bool."""
    k = _expand_kv(k, q.shape[2])
    v = _expand_kv(v, q.shape[2])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits * _scale(cfg)
    logits = softcap(logits, cfg.attn_softcap)
    if mask.ndim == 3:
        mask = mask[:, None]          # (b, 1, sq, sk)
    else:
        mask = mask[None, None]       # (1, 1, sq, sk)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attend_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                   q_pos: jax.Array, k_pos: jax.Array, cfg: ModelConfig,
                   *, causal: bool, window: int, chunk: int = 1024) -> jax.Array:
    """Online-softmax attention over KV chunks, ``chunk`` queries at a time.

    q_pos: (sq,) absolute positions of queries; k_pos: (sk,) of keys
    (-1 marks an empty cache slot).  Semantics identical to attend_dense
    with mask built from positions.  Memory is O(chunk * chunk) scores a
    head in the backward pass too: it recomputes each block of queries'
    scores, chunk by chunk, rather than keeping them.
    """
    b, sq, h, hd = q.shape
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    n_q = -(-sq // chunk)
    if n_q == 1:
        return _attend_kv_chunks(q, k, v, q_pos, k_pos, cfg, causal=causal,
                                 window=window, chunk=chunk)
    pad = n_q * chunk - sq
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pb = jnp.pad(q_pos, (0, pad), constant_values=-1)
    qb = jnp.moveaxis(qb.reshape(b, n_q, chunk, h, hd), 1, 0)

    @jax.checkpoint
    def block(args):
        qc, pc = args
        return _attend_kv_chunks(qc, k, v, pc, k_pos, cfg, causal=causal,
                                 window=window, chunk=chunk)

    out = jax.lax.map(block, (qb, pb.reshape(n_q, chunk)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, n_q * chunk, h, -1)
    return out[:, :sq]


def _attend_kv_chunks(q, k, v, q_pos, k_pos, cfg: ModelConfig, *,
                      causal: bool, window: int, chunk: int) -> jax.Array:
    """``attend_chunked`` for one block of queries: a scan over KV chunks
    with an online softmax.  k, v already expanded to q's heads."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    vd = v.shape[-1]            # may differ from hd (MLA: v_head_dim)
    scale = _scale(cfg)

    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=-1)

    k = k.reshape(b, n_chunks, chunk, h, hd)
    v = v.reshape(b, n_chunks, chunk, h, vd)
    k_pos = k_pos.reshape(n_chunks, chunk)

    def body(carry, inputs):
        m_prev, l_prev, acc = carry
        kc, vc, kp = inputs              # (b, chunk, h, hd), (chunk,)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                            preferred_element_type=jnp.float32) * scale
        logits = softcap(logits, cfg.attn_softcap)
        valid = kp[None, :] >= 0
        if causal:
            valid = valid & (kp[None, :] <= q_pos[:, None])
        if window > 0:
            valid = valid & (kp[None, :] > q_pos[:, None] - window)
        logits = jnp.where(valid[None, None], logits, NEG_INF)
        m_cur = jnp.max(logits, axis=-1)                     # (b, h, q)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(q.dtype), vc).astype(jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, vd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), (m0, l0, a0),
        (jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0), k_pos))
    out = acc / jnp.maximum(l, 1e-37)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # (b, sq, h, hd)


# --------------------------------------------------------------------------
# Layer-level forward (full sequence: train / prefill)
# --------------------------------------------------------------------------

# sequences at or above this length use the chunked path under jit
CHUNKED_THRESHOLD = 8192

# perf knob (hillclimb): force the online-softmax chunked path for ALL
# sequence lengths (never materialise (sq, sk) score tensors in HBM) —
# the XLA-portable analogue of running the Pallas flash kernel.
import threading as _threading

_ATTN_IMPL = _threading.local()


def set_attention_impl(impl: str):
    """'auto' (dense below CHUNKED_THRESHOLD) or 'chunked' (always)."""
    _ATTN_IMPL.impl = impl


def _use_chunked(s: int) -> bool:
    impl = getattr(_ATTN_IMPL, "impl", "auto")
    return impl == "chunked" or s >= CHUNKED_THRESHOLD


def _project_qkv(p: Dict, x: jax.Array, cfg: ModelConfig,
                 kv_src: Optional[jax.Array] = None):
    src = x if kv_src is None else kv_src
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return shard_heads(q), k, v


def self_attention(p: Dict, x: jax.Array, positions: jax.Array,
                   cfg: ModelConfig, *, window: int = 0,
                   use_rope: bool = True) -> jax.Array:
    """Causal self attention over a full sequence.  x: (b, s, d)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg)
    if use_rope and cfg.pos_embedding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)

    pos = positions[0] if positions.ndim == 2 else positions
    if _use_chunked(s):
        out = attend_chunked(q, k, v, pos, pos, cfg, causal=True,
                             window=window)
    else:
        mask = pos[:, None] >= pos[None, :]
        if window > 0:
            mask &= pos[:, None] - pos[None, :] < window
        out = attend_dense(q, k, v, mask, cfg)
    out = shard_heads(out)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def cross_attention(p: Dict, x: jax.Array, memory: jax.Array,
                    cfg: ModelConfig, *, gated: bool = False,
                    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None
                    ) -> jax.Array:
    """Encoder-decoder / vision cross attention (no mask, no rope)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    if kv_override is not None:
        k, v = kv_override
    else:
        k, v = cross_kv(p, memory, cfg, x.dtype)
    if gated:
        q = common.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = common.rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = shard_heads(q)
    mask = jnp.ones((x.shape[1], k.shape[1]), bool)
    out = attend_dense(q, k, v, mask, cfg)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    if gated:
        out = jnp.tanh(p["gate"].astype(x.dtype)) * out
    return out


def cross_kv(p: Dict, memory: jax.Array, cfg: ModelConfig, dtype):
    """Precompute cross-attn K/V from encoder memory (cached at prefill)."""
    k = jnp.einsum("bsd,dhk->bshk", memory.astype(dtype), p["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", memory.astype(dtype), p["wv"].astype(dtype))
    if "bk" in p:
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    return k, v


# --------------------------------------------------------------------------
# KV cache (ring buffer) — prefill & decode
# --------------------------------------------------------------------------

def cache_len(cfg: ModelConfig, kind: str, max_len: int) -> int:
    if kind == "local" and cfg.local_window:
        return min(cfg.local_window, max_len)
    return max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int, kind: str,
               dtype=jnp.bfloat16) -> Dict:
    w = cache_len(cfg, kind, max_len)
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, w, kv, hd), dtype),
        "v": jnp.zeros((batch, w, kv, hd), dtype),
        "pos": jnp.full((w,), -1, jnp.int32),
    }


def prefill_attention(p: Dict, x: jax.Array, positions: jax.Array,
                      cfg: ModelConfig, cache: Dict, *, window: int = 0
                      ) -> Tuple[jax.Array, Dict]:
    """Full-sequence attention that also fills the ring cache."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg)
    if cfg.pos_embedding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)

    pos = positions[0] if positions.ndim == 2 else positions
    if _use_chunked(s):
        out = attend_chunked(q, k, v, pos, pos, cfg, causal=True, window=window)
    else:
        mask = pos[:, None] >= pos[None, :]
        if window > 0:
            mask &= pos[:, None] - pos[None, :] < window
        out = attend_dense(q, k, v, mask, cfg)
    out = jnp.einsum("bshk,hkd->bsd", shard_heads(out), p["wo"].astype(x.dtype))

    w = cache["k"].shape[1]
    if s >= w:
        # keep the last w entries, laid out by the ring invariant
        # slot(p) = p % w so later decode writes evict the oldest entry
        shift = (s - w) % w
        cache = {
            "k": jnp.roll(k[:, s - w:], shift, axis=1).astype(cache["k"].dtype),
            "v": jnp.roll(v[:, s - w:], shift, axis=1).astype(cache["v"].dtype),
            "pos": jnp.roll(pos[s - w:], shift).astype(jnp.int32),
        }
    else:
        cache = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
            "pos": jax.lax.dynamic_update_slice(
                cache["pos"], pos.astype(jnp.int32), (0,)),
        }
    return out, cache


def decode_attention(p: Dict, x: jax.Array, position: jax.Array,
                     cfg: ModelConfig, cache: Dict, *, window: int = 0
                     ) -> Tuple[jax.Array, Dict]:
    """Single-token decode step against the ring cache.

    x: (b, 1, d); position: scalar int32 (same step for the whole batch —
    the serving model runs synchronous batched decode).
    """
    q, k, v = _project_qkv(p, x, cfg)
    if cfg.pos_embedding == "rope":
        posb = jnp.full((1,), 0, jnp.int32) + position
        q = apply_rope(q, posb[None, :], cfg.rope_theta, cfg.rope_fraction)
        k = apply_rope(k, posb[None, :], cfg.rope_theta, cfg.rope_fraction)

    w = cache["k"].shape[1]
    slot = position % w
    k_cache = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    pos_cache = jax.lax.dynamic_update_slice(
        cache["pos"], position[None].astype(jnp.int32), (slot,))

    valid = (pos_cache >= 0) & (pos_cache <= position)
    if window > 0:
        valid &= pos_cache > position - window
    mask = jnp.broadcast_to(valid[None, :], (1, w))       # (sq=1, sk=w)
    out = attend_dense(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                       mask, cfg)
    out = jnp.einsum("bshk,hkd->bsd", shard_heads(out), p["wo"].astype(x.dtype))
    return out, {"k": k_cache, "v": v_cache, "pos": pos_cache}
