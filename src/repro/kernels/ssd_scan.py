"""Mamba2 SSD chunked scan — Pallas TPU kernel.

TPU-native reformulation of the paper's GPU SSD kernel (arXiv:2405.21060):
the sequence is split into chunks; each chunk contributes

  * an intra-chunk quadratic term  Y_diag = (C B^T ⊙ decay ⊙ causal)(dt x)
    — two MXU matmuls over (L x N)/(L x L) tiles, and
  * an inter-chunk linear recurrence on the (P x N) state, carried across
    the sequential chunk grid dimension in VMEM scratch.

grid = (batch, heads, n_chunks) with the chunk dim "arbitrary"
(sequential); the state scratch is re-initialised at chunk 0.  VMEM
working set per cell ≈ L*(P+2N)*4B + L*L*4B + P*N*4B — with L=chunk=128,
P=64, N=128: ~230 KiB.

Assumes ngroups == 1 (mamba2-130m) — B/C are shared across heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_HI = jax.lax.Precision.HIGHEST


def _ssd_kernel(x_ref, dtr_ref, dtc_ref, alog_ref, b_ref, c_ref, y_ref,
                state_scr, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # (L, P)
    dt_row = dtr_ref[0, 0].astype(jnp.float32)   # (1, L)
    dt_col = dtc_ref[0, 0].astype(jnp.float32)   # (L, 1)
    a = -jnp.exp(alog_ref[0].astype(jnp.float32))  # (1, 1) A of this head
    bmat = b_ref[0].astype(jnp.float32)          # (L, N)
    cmat = c_ref[0].astype(jnp.float32)          # (L, N)

    # inclusive prefix sums of the log-decay steps, as triangular
    # matmuls in both layouts (cs_i = sum_{j<=i} dt_j * A)
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = li >= lj
    cs_col = jax.lax.dot_general(lower.astype(jnp.float32), dt_col * a,
                                 (((1,), (0,)), ((), ())), precision=_HI,
                                 preferred_element_type=jnp.float32)
    cs_row = jax.lax.dot_general(dt_row * a, (li <= lj).astype(jnp.float32),
                                 (((1,), (0,)), ((), ())), precision=_HI,
                                 preferred_element_type=jnp.float32)

    # intra-chunk: decay(i<-j) = exp(cs_i - cs_j), lower triangular
    decay = jnp.where(lower, jnp.exp(cs_col - cs_row), 0.0)

    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    gated = scores * decay * dt_row              # (L, L) apply dt_j
    y_diag = jax.lax.dot_general(gated, x, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    # off-diagonal: state entering the chunk
    state = state_scr[...]                       # (P, N)
    y_off = jax.lax.dot_general(cmat, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_off = y_off * jnp.exp(cs_col)              # (L, P), includes own step

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: S' = S * exp(sum da) + sum_l exp(cs_L - cs_l) dt_l x_l B_l
    total = jnp.sum(dt_row * a, axis=1, keepdims=True)   # (1, 1)
    coeff = jnp.exp(total - cs_col) * dt_col     # (L, 1)
    upd = jax.lax.dot_general(x * coeff, bmat,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    state_scr[...] = state * jnp.exp(total) + upd


def ssd_scan_pallas(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                    B: jax.Array, C: jax.Array, *, chunk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """x: (b, s, h, p); dt: (b, s, h) post-softplus; a_log: (h,);
    B, C: (b, s, 1, n).  Returns y (b, s, h, p).  s % chunk == 0.
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert B.shape[2] == 1, "pallas ssd kernel assumes ngroups == 1"
    assert s % chunk == 0
    nc = s // chunk

    xt = jnp.transpose(x, (0, 2, 1, 3))          # (b, h, s, p)
    dtt = jnp.transpose(dt, (0, 2, 1))           # (b, h, s)
    bt = B[:, :, 0, :]                           # (b, s, n)
    ct = C[:, :, 0, :]

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1, 1, chunk, 1), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1), lambda ib, ih, ic: (ih, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda ib, ih, ic: (ib, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda ib, ih, ic: (ib, ih, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt[:, :, None, :], dtt[:, :, :, None], a_log.reshape(h, 1, 1),
      bt, ct)
    return jnp.transpose(y, (0, 2, 1, 3))
