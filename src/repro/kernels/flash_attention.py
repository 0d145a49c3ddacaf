"""Fused flash attention — Pallas TPU kernel.

Streaming-softmax attention with causal masking, sliding windows, logit
soft-capping and GQA, tiled for VMEM:

  grid = (batch, q_heads, q_blocks, kv_blocks); the kv dimension is
  sequential ("arbitrary") — running max / denominator / accumulator live
  in VMEM scratch and are re-initialised at kv_block 0.  Block shapes are
  MXU-aligned (q_block x head_dim and kv_block x head_dim with head_dim a
  multiple of 128 where the arch allows; q/kv blocks default 128/128 —
  working set per grid cell = (qb + 2*kb) * hd * 2B + qb*kb*4B
  ≈ 128*128*4 + 3*128*128*2 ≈ 160 KiB, far under the ~16 MiB VMEM budget,
  leaving room for double buffering).

GQA is expressed in the k/v BlockSpec index maps (q head -> kv head), so
KV blocks are fetched once per q-head group position without a
materialised repeat.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int, softcap: float,
                 q_block: int, kv_block: int, n_kv_blocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = iq * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 0)
    k_pos = ik * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 1)

    # skip fully-masked kv blocks (beyond the causal/window horizon)
    q_lo = iq * q_block
    q_hi = q_lo + q_block - 1
    k_lo = ik * kv_block
    needed = jnp.bool_(True)
    if causal:
        needed = needed & (k_lo <= q_hi)
    if window > 0:
        k_hi = k_lo + kv_block - 1
        needed = needed & (k_hi > q_lo - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (qb, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (kb, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.bool_(True)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window > 0:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                            # (qb, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-37)).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           scale: float, causal: bool = True,
                           window: int = 0, softcap: float = 0.0,
                           q_block: int = 128, kv_block: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: (b, h, sq, hd); k/v: (b, kv, sk, hd) with h % kv == 0.

    Returns (b, h, sq, hd) in q.dtype.  sq/sk must be multiples of the
    block sizes (wrappers pad).
    """
    b, h, sq, hd = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    assert h % kvh == 0 and sq % q_block == 0 and sk % kv_block == 0
    group = h // kvh
    nq = sq // q_block
    nk = sk // kv_block

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, q_block=q_block, kv_block=kv_block, n_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, q_block, hd),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, kv_block, hd),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, kv_block, hd),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, hd),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
        name="sage_flash_attention",
    )(q, k, v)
