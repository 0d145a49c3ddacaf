"""Mamba2 block — SSD (state-space duality) sequence mixing.

Full-sequence path uses the chunked SSD algorithm (arXiv:2405.21060 §6):
intra-chunk attention-like matmuls + inter-chunk state recurrence, which is
also what the Pallas kernel (`repro.kernels.ssd_scan`) implements with
VMEM-tiled blocks.  ``ssd_reference`` is the per-timestep sequential oracle.

The gated output norm normalises each of the ``ssm_ngroups`` groups of
the inner width on its own (``common.group_rms_norm``).

Decode carries (state, conv_tail): state (b, H, P, N), conv tail
(b, convw-1, conv_dim) — O(1) per token, which is why mamba2 runs the
long_500k cell.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.common import dense_init


def d_inner(cfg: ModelConfig) -> int:
    if cfg.ssm_nheads:
        return cfg.ssm_nheads * cfg.ssm_headdim
    return cfg.ssm_expand * cfg.d_model


def n_ssm_heads(cfg: ModelConfig) -> int:
    return d_inner(cfg) // cfg.ssm_headdim


def conv_dim(cfg: ModelConfig) -> int:
    return d_inner(cfg) + 2 * cfg.ssm_ngroups * cfg.ssm_state


def init_ssm(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    d = cfg.d_model
    di = d_inner(cfg)
    h = n_ssm_heads(cfg)
    cd = conv_dim(cfg)
    ks = common.split_keys(key, 5)
    proj_out = 2 * di + 2 * cfg.ssm_ngroups * cfg.ssm_state + h
    return {
        "in_proj": dense_init(ks[0], (d, proj_out), dtype=dtype),
        "conv_w": dense_init(ks[1], (cfg.ssm_conv, cd), dtype=dtype),
        "conv_b": jnp.zeros((cd,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm": jnp.ones((di,), dtype),
        "out_proj": dense_init(ks[2], (di, d), dtype=dtype),
    }


def _split_proj(cfg: ModelConfig, zxbcdt: jax.Array):
    di = d_inner(cfg)
    gn = cfg.ssm_ngroups * cfg.ssm_state
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di: di + di + 2 * gn]
    dt = zxbcdt[..., di + di + 2 * gn:]
    return z, xbc, dt


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv along seq.  xbc: (b, s, cd); w: (k, cd)."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i: i + xbc.shape[1], :] * w[i] for i in range(k))
    return jax.nn.silu(out + b)


def _conv_step(tail: jax.Array, x_new: jax.Array, w: jax.Array,
               b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Single-token depthwise conv. tail: (b, k-1, cd); x_new: (b, cd)."""
    window = jnp.concatenate([tail, x_new[:, None, :]], axis=1)  # (b, k, cd)
    out = jnp.einsum("bkc,kc->bc", window, w.astype(x_new.dtype)) + b
    return jax.nn.silu(out), window[:, 1:, :]


# --------------------------------------------------------------------------
# SSD core
# --------------------------------------------------------------------------

def _segsum(log_a: jax.Array) -> jax.Array:
    """(..., L) -> (..., L, L) lower-triangular segment sums."""
    L = log_a.shape[-1]
    cs = jnp.cumsum(log_a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]     # sum over (j, i]
    mask = jnp.tril(jnp.ones((L, L), bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                B: jax.Array, C: jax.Array, chunk: int,
                initial_state: jax.Array | None = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    x: (b, s, h, p); dt: (b, s, h) (post-softplus); a_log: (h,) (A = -exp);
    B, C: (b, s, g, n) with h % g == 0.  Returns (y (b,s,h,p),
    final_state (b,h,p,n)).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    nc = -(-s // chunk)
    pad = nc * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))

    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)                 # (b, S, h, n)
    Ch = jnp.repeat(C, rep, axis=2)

    def rs(t, feat):                                 # (b,S,h,*) -> (b,nc,L,h,*)
        return t.reshape(b, nc, chunk, *feat)

    xc = rs(x, (h, p))
    dtc = rs(dt, (h,))
    Bc = rs(Bh, (h, n))
    Cc = rs(Ch, (h, n))

    A = -jnp.exp(a_log)                              # (h,)
    dA = dtc * A                                     # (b,nc,L,h) log-decay
    dA = jnp.moveaxis(dA, 3, 2)                      # (b,nc,h,L)

    # intra-chunk (diagonal blocks): Y = (C B^T . decay . causal) @ (dt*x)
    seg = _segsum(dA)                                # (b,nc,h,L,L)
    decay = jnp.exp(seg)
    scores = jnp.einsum("bclhn,bcshn->bchls", Cc, Bc)
    y_diag = jnp.einsum("bchls,bchls,bcsh,bcshp->bclhp",
                        scores, decay.astype(scores.dtype),
                        dtc.astype(scores.dtype), xc)

    # chunk-final states: S_c = sum_t a(t->end) * dt_t * B_t (x) x_t
    decay_to_end = jnp.exp(jnp.cumsum(dA[..., ::-1], axis=-1)[..., ::-1] - dA)
    states = jnp.einsum("bchl,bclh,bclhn,bclhp->bchpn",
                        decay_to_end.astype(scores.dtype),
                        dtc.astype(scores.dtype), Bc, xc)

    # inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(jnp.sum(dA, axis=-1))      # (b,nc,h)

    def scan_fn(carry, inp):
        st, dec = inp                                # (b,h,p,n), (b,h)
        new = carry * dec[..., None, None].astype(carry.dtype) + st
        return new, carry                            # emit state *entering* chunk

    init = (jnp.zeros((b, h, p, n), scores.dtype)
            if initial_state is None else initial_state.astype(scores.dtype))
    final, prev_states = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)    # (b,nc,h,p,n)

    # off-diagonal contribution: C_t · decay(start->t) · S_prev
    decay_from_start = jnp.exp(jnp.cumsum(dA, axis=-1))  # includes own step
    y_off = jnp.einsum("bclhn,bchl,bchpn->bclhp",
                       Cc, decay_from_start.astype(scores.dtype), prev_states)

    y = (y_diag + y_off).reshape(b, nc * chunk, h, p)[:, :s]
    return y, final


def ssd_reference(x, dt, a_log, B, C, initial_state=None):
    """Sequential per-timestep oracle (tests)."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32)
    A = -jnp.exp(a_log)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)

    def step(state, t):
        xt, dtt, Bt, Ct = t
        a = jnp.exp(dtt * A)[:, :, None, None]       # (b,h,1,1)
        upd = jnp.einsum("bh,bhn,bhp->bhpn", dtt, Bt, xt)
        state = state * a + upd
        y = jnp.einsum("bhn,bhpn->bhp", Ct, state)
        return state, y

    init = (jnp.zeros((b, h, p, n), jnp.float32) if initial_state is None
            else initial_state.astype(jnp.float32))
    final, ys = jax.lax.scan(
        step, init,
        (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
         jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0)))
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), final


# --------------------------------------------------------------------------
# Block-level forward
# --------------------------------------------------------------------------

def ssm_block(p: Dict, x: jax.Array, cfg: ModelConfig, *,
              use_kernel: bool = False) -> jax.Array:
    """Full-sequence Mamba2 mixer.  x: (b, s, d) (already normed)."""
    y, _ = _ssm_forward(p, x, cfg, initial_state=None, use_kernel=use_kernel)
    return y


def _ssm_forward(p: Dict, x: jax.Array, cfg: ModelConfig, *,
                 initial_state, use_kernel: bool):
    b, s, _ = x.shape
    di = d_inner(cfg)
    h = n_ssm_heads(cfg)
    g, n = cfg.ssm_ngroups, cfg.ssm_state

    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    z, xbc, dt = _split_proj(cfg, zxbcdt)
    xbc = _causal_conv(xbc, p["conv_w"].astype(x.dtype), p["conv_b"].astype(x.dtype))
    xs = xbc[..., :di].reshape(b, s, h, cfg.ssm_headdim)
    B = xbc[..., di: di + g * n].reshape(b, s, g, n)
    C = xbc[..., di + g * n:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])

    if use_kernel:
        from repro.kernels import ops
        y, final = ops.ssd_scan(xs, dt, p["a_log"], B, C, chunk=cfg.ssm_chunk)
    else:
        y, final = ssd_chunked(xs, dt, p["a_log"], B, C, chunk=cfg.ssm_chunk,
                               initial_state=initial_state)
    y = y + xs * p["d_skip"].astype(x.dtype)[:, None]
    y = y.reshape(b, s, di)
    y = common.group_rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps,
                              cfg.ssm_ngroups)
    y = common.shard_ff(y)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(x.dtype))
    return out, final


# --------------------------------------------------------------------------
# Decode (O(1) state)
# --------------------------------------------------------------------------

def init_ssm_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> Dict:
    h = n_ssm_heads(cfg)
    return {
        "state": jnp.zeros((batch, h, cfg.ssm_headdim, cfg.ssm_state), dtype),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim(cfg)), dtype),
    }


def ssm_prefill(p: Dict, x: jax.Array, cfg: ModelConfig, cache: Dict
                ) -> Tuple[jax.Array, Dict]:
    b, s, _ = x.shape
    out, final = _ssm_forward(p, x, cfg, initial_state=cache["state"],
                              use_kernel=False)
    # conv tail: last (k-1) pre-conv xbc values
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    _, xbc, _ = _split_proj(cfg, zxbcdt)
    km1 = cfg.ssm_conv - 1
    tail = xbc[:, -km1:, :].astype(cache["conv"].dtype)
    return out, {"state": final.astype(cache["state"].dtype), "conv": tail}


def ssm_decode(p: Dict, x: jax.Array, cfg: ModelConfig, cache: Dict
               ) -> Tuple[jax.Array, Dict]:
    """Single-token step.  x: (b, 1, d)."""
    b = x.shape[0]
    di = d_inner(cfg)
    h = n_ssm_heads(cfg)
    g, n = cfg.ssm_ngroups, cfg.ssm_state

    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    z, xbc, dt = _split_proj(cfg, zxbcdt)
    xbc_t, conv_tail = _conv_step(cache["conv"].astype(x.dtype), xbc[:, 0],
                                  p["conv_w"].astype(x.dtype),
                                  p["conv_b"].astype(x.dtype))
    xs = xbc_t[..., :di].reshape(b, h, cfg.ssm_headdim)
    B = xbc_t[..., di: di + g * n].reshape(b, g, n)
    C = xbc_t[..., di + g * n:].reshape(b, g, n)
    dtt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (b,h)

    rep = h // g
    Bh = jnp.repeat(B, rep, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(C, rep, axis=1).astype(jnp.float32)
    A = -jnp.exp(p["a_log"])
    a = jnp.exp(dtt * A)                                   # (b,h)
    state = cache["state"].astype(jnp.float32)
    state = state * a[..., None, None] + jnp.einsum(
        "bh,bhn,bhp->bhpn", dtt, Bh, xs.astype(jnp.float32))
    y = jnp.einsum("bhn,bhpn->bhp", Ch, state).astype(x.dtype)
    y = y + xs * p["d_skip"].astype(x.dtype)[None, :, None]
    y = y.reshape(b, 1, di)
    y = common.group_rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps,
                              cfg.ssm_ngroups)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(x.dtype))
    return out, {"state": state.astype(cache["state"].dtype),
                 "conv": conv_tail.astype(cache["conv"].dtype)}
