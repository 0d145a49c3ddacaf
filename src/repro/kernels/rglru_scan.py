"""RG-LRU linear recurrence — Pallas TPU kernel.

h_t = a_t * h_{t-1} + x_t over the sequence, tiled as
grid = (batch, width_blocks, seq_chunks): the chunk dimension is
sequential with the (1, wb) hidden state carried in VMEM scratch; within
a chunk the recurrence runs as a fori_loop of VPU vector ops over the
chunk's rows (a cumprod reformulation was tried and rejected: P_t
underflows fp32 for small gates — recorded in EXPERIMENTS §Perf notes).

Width blocks default 512 lanes: working set per cell = 3 * L * wb * 4B
≈ 1.5 MiB at L=256 — VMEM-resident, the recurrence never touches HBM
between steps (the whole point of the kernel vs the XLA associative
scan, which materialises log-depth intermediates).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, x_ref, h_ref, state_scr, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    def body(t, h):                              # h: (1, wb)
        row = pl.ds(t, 1)
        h = (a_ref[0, row, :].astype(jnp.float32) * h
             + x_ref[0, row, :].astype(jnp.float32))
        h_ref[0, row, :] = h.astype(h_ref.dtype)
        return h

    h_final = jax.lax.fori_loop(0, chunk, body, state_scr[...])
    state_scr[...] = h_final


def rglru_scan_pallas(a: jax.Array, x: jax.Array, h0=None, *,
                      chunk: int = 256, width_block: int = 512,
                      interpret: bool = False) -> jax.Array:
    """a, x: (b, s, w) fp32; optional h0 (b, w).  Returns h (b, s, w)."""
    b, s, w = a.shape
    assert s % chunk == 0
    wb = min(width_block, w)
    assert w % wb == 0
    if h0 is not None:
        # fold h0 into the first step: x0' = x0 + a0 * h0
        x = x.at[:, 0].add(a[:, 0] * h0)

    kernel = functools.partial(_rglru_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(b, w // wb, s // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, wb), lambda ib, iw, ic: (ib, ic, iw)),
            pl.BlockSpec((1, chunk, wb), lambda ib, iw, ic: (ib, ic, iw)),
        ],
        out_specs=pl.BlockSpec((1, chunk, wb),
                               lambda ib, iw, ic: (ib, ic, iw)),
        out_shape=jax.ShapeDtypeStruct((b, s, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, wb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, x)
