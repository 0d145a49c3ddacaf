"""Batched heat scoring + Markov next-access prediction — the
*prediction* stage of SAGE's percipience loop (the paper's title claim:
storage that anticipates access instead of only reacting to it).

The heat of an object is an exponentially-decayed access count,

    heat(now) = sum_i w_i * exp(-lambda * (now - t_i)),   lambda = ln2 / T½

over its access timestamps t_i.  Evaluated as a linear recurrence over
the (time-ordered) access history,

    h_i = exp(-lambda * (t_i - t_{i-1})) * h_{i-1} + w_i,

which is the rglru_scan idiom: grid over object blocks, fori_loop over
history steps, the running heat vector living in registers/VMEM — one
kernel launch scores every tracked object.  CPU containers run the same
kernel body with ``interpret=True`` (kernels/ops.py-style dispatch).

Gap/decay precomputation happens in float64 numpy — epoch-second
timestamps do not survive float32 — only the decay factors (all in
[0, 1]) and weights are handed to the f32 kernel.
"""
from __future__ import annotations

import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LN2 = math.log(2.0)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _heat_kernel(a_ref, x_ref, out_ref, *, hist: int):
    """a, x: (hist, ob) decay factors / weights, oldest step first;
    out: (1, ob) final heat after the last access of each object.  Each
    step reads its (1, ob) row from the refs."""
    def body(t, h):                       # h: (1, ob)
        return a_ref[pl.ds(t, 1), :] * h + x_ref[pl.ds(t, 1), :]

    out_ref[...] = jax.lax.fori_loop(
        0, hist, body, jnp.zeros_like(out_ref))


@functools.lru_cache(maxsize=512)
def _heat_call(hist: int, nobj: int, obj_block: int, interpret: bool):
    """Jitted heat scan for one (hist, nobj) shape — cached like the
    analytics kernels' builders, so a refresh at a recurring padded
    shape compiles nothing."""
    kernel = functools.partial(_heat_kernel, hist=hist)
    call = pl.pallas_call(
        kernel,
        grid=(nobj // obj_block,),
        in_specs=[
            pl.BlockSpec((hist, obj_block), lambda i: (0, i)),
            pl.BlockSpec((hist, obj_block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, obj_block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, nobj), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="sage_heat_scan",
    )

    def sage_heat_scan(a, x):
        return call(a, x)[0]
    return jax.jit(sage_heat_scan)


def heat_scan_pallas(a: jax.Array, x: jax.Array, *, obj_block: int = 128,
                     interpret: bool = False) -> jax.Array:
    """a, x: (hist, nobj) f32 with hist % 8 == 0, nobj % obj_block == 0.
    Returns (nobj,) f32 heat at each object's last access."""
    hist, nobj = a.shape
    assert nobj % obj_block == 0 and hist % 8 == 0
    return _heat_call(hist, nobj, obj_block, interpret)(a, x)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def heat_scores(timestamps: np.ndarray, mask: np.ndarray, now: float,
                half_life_s: float = 120.0,
                weights: Optional[np.ndarray] = None,
                interpret: bool = False) -> np.ndarray:
    """Heat for every object from its access-timestamp history.

    timestamps/mask (and optional per-access weights): (nobj, hist),
    right-aligned as produced by FeatureExtractor.history_tensors.
    Returns (nobj,) f64 heat as of ``now``.
    """
    ts = np.asarray(timestamps, np.float64)
    m = np.asarray(mask, np.float64)
    n, hist = ts.shape
    if n == 0:
        return np.zeros((0,), np.float64)
    lam = LN2 / half_life_s
    w = m if weights is None else np.asarray(weights, np.float64) * m

    # decay factor per step: exp(-lam * gap to previous access); padded /
    # leading steps get a=1, x=0 (identity, the rglru padding trick)
    prev = np.concatenate([ts[:, :1], ts[:, :-1]], axis=1)
    gaps = np.clip(ts - prev, 0.0, None)
    a = np.where(m > 0, np.exp(-lam * gaps), 1.0)
    # first valid access decays h=0, so its factor is irrelevant; clamp it
    # to 1 to avoid exp underflow noise on huge epoch-vs-0 gaps
    first = np.argmax(m, axis=1)
    has = m.any(axis=1)
    a[np.arange(n), first] = np.where(has, 1.0, a[np.arange(n), first])

    # (hist, nobj) layout, padded to kernel tile multiples (f32 min tile
    # is (8, 128)); a=1/x=0 padding is the identity step
    at = np.ascontiguousarray(a.T, np.float32)
    xt = np.ascontiguousarray(w.T, np.float32)
    ob = 128
    ph, pn = (-hist) % 8, (-n) % ob
    if ph or pn:
        at = np.pad(at, ((0, ph), (0, pn)), constant_values=1.0)
        xt = np.pad(xt, ((0, ph), (0, pn)))

    h_last = np.asarray(heat_scan_pallas(
        jnp.asarray(at), jnp.asarray(xt), obj_block=ob,
        interpret=interpret or not _on_tpu()), np.float64)[:n]

    # decay from each object's last access to `now` (f64, outside kernel)
    t_last = (ts * m).max(axis=1)
    tail = np.where(has, np.exp(-lam * np.clip(now - t_last, 0.0, None)), 0.0)
    return h_last * tail


def heat_scores_ref(timestamps: np.ndarray, mask: np.ndarray, now: float,
                    half_life_s: float = 120.0,
                    weights: Optional[np.ndarray] = None) -> np.ndarray:
    """Pure-numpy closed form: sum_i w_i * 2^-((now - t_i)/T½)."""
    ts = np.asarray(timestamps, np.float64)
    m = np.asarray(mask, np.float64)
    lam = LN2 / half_life_s
    w = m if weights is None else np.asarray(weights, np.float64) * m
    return (w * np.exp(-lam * np.clip(now - ts, 0.0, None)) * (m > 0)
            ).sum(axis=1)


# ---------------------------------------------------------------------------
# Markov next-access prediction
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k",))
def markov_topk(probs: jax.Array, current: jax.Array, k: int = 3
                ) -> Tuple[jax.Array, jax.Array]:
    """Batched top-k next-bucket prediction.

    probs: (B, B) row-normalised transition matrix; current: (m,) int
    bucket indices.  Returns (values, indices), each (m, k).
    """
    rows = probs[current]                     # (m, B)
    return jax.lax.top_k(rows, k)


def markov_predict(probs: np.ndarray, current: int, k: int = 3,
                   min_p: float = 0.0) -> List[Tuple[int, float]]:
    """Top-k (bucket, probability) successors of ``current``, filtered to
    probability > min_p.  Thin convenience over markov_topk."""
    vals, idxs = markov_topk(jnp.asarray(probs, jnp.float32),
                             jnp.asarray([current]), k=k)
    out = []
    for p, b in zip(np.asarray(vals[0]), np.asarray(idxs[0])):
        if p > min_p:
            out.append((int(b), float(p)))
    return out
