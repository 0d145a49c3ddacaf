"""Plain reference of Mamba-2 language-model training (arXiv:2405.21060).

Straightforward ``jax.numpy`` in float32 with full-precision matrix
products, in the benchmark's weight layout (``bench.gen.mamba2``); it
imports nothing of the program.  Per layer, with x the residual stream:

    h = rmsnorm(x) * ln
    z, xBC, dt = split(h @ in_proj)
    xBC = silu(causal depthwise conv(xBC, conv_w) + conv_b)
    xs, B, C = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(a_log)
    y = SSD(xs * dt, A * dt, B, C) + xs * D        (the paper's Listing 1)
    x = x + (rmsnorm(y * silu(z)) * norm) @ out_proj

then logits = (rmsnorm(x) * ln_f) @ embed^T (tied) and the mean token
cross entropy.  ``train`` takes AdamW steps with global-norm clipping,
bias correction and the linear-warmup cosine schedule of the
configuration's ``train`` block.

``mm_dtype`` names the precision of the matrix products' inputs; the
default float32 is the reference, ``float8_e4m3fn`` the lower-precision
control (inputs rounded to fp8, products accumulated in float32).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import numpy as np

from bench.gen.mamba2 import dims

# weight decay falls on the matrices, never on norms, biases or scalars
DECAYED = ("embed", "in_proj", "conv_w", "out_proj")


def _mm(a, b, mm_dtype):
    import jax
    import jax.numpy as jnp
    if mm_dtype == "float32":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    dt = jnp.dtype(mm_dtype)
    return jnp.matmul(a.astype(dt), b.astype(dt),
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * scale


def _segsum(x):
    import jax.numpy as jnp
    T = x.shape[-1]
    cs = jnp.cumsum(x, -1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), seg, -jnp.inf)


def ssd(X, A, B, C, chunk: int):
    """Listing 1 of the paper (``ssd_minimal_discrete``), zero initial
    state.  X (b, l, h, p), A (b, l, h), B and C (b, l, h, n)."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    b, l, h, p = X.shape
    c = l // chunk
    X = X.reshape(b, c, chunk, h, p)
    B = B.reshape(b, c, chunk, h, -1)
    C = C.reshape(b, c, chunk, h, -1)
    A = jnp.moveaxis(A.reshape(b, c, chunk, h), -1, 1)      # b h c l
    A_cs = jnp.cumsum(A, -1)
    L = jnp.exp(_segsum(A))
    Y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", C, B, L, X,
                        precision=hi)
    decay_states = jnp.exp(A_cs[..., -1:] - A_cs)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", B, decay_states, X,
                        precision=hi)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(_segsum(jnp.pad(A_cs[..., -1], ((0, 0), (0, 0),
                                                          (1, 0)))))
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states,
                            precision=hi)[:, :-1]
    Y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", C, new_states,
                       jnp.exp(A_cs), precision=hi)
    return (Y_diag + Y_off).reshape(b, l, h, p)


def _layer(x, p, cfg, m, mm_dtype):
    import jax
    import jax.numpy as jnp
    eps = cfg["norm_eps"]
    b, s, _ = x.shape
    di, gn, h = m["di"], m["gn"], m["h"]
    zxbcdt = _mm(_rms(x, p["ln"], eps), p["in_proj"], mm_dtype)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * gn],
                  zxbcdt[..., 2 * di + 2 * gn:])
    k = m["k"]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + s] * p["conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :di].reshape(b, s, h, cfg["headdim"])
    g = cfg["ngroups"]
    B = jnp.repeat(xbc[..., di:di + gn].reshape(b, s, g, -1), h // g, 2)
    C = jnp.repeat(xbc[..., di + gn:].reshape(b, s, g, -1), h // g, 2)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["a_log"])
    y = ssd(xs * dt[..., None], A * dt, B, C, cfg["chunk_size"])
    y = (y + xs * p["d_skip"][:, None]).reshape(b, s, di)
    y = _rms(y * jax.nn.silu(z), p["norm"], eps)
    return x + _mm(y, p["out_proj"], mm_dtype)


def loss(w, tokens, labels, cfg: Dict, mm_dtype: str = "float32"):
    """Mean token cross entropy of one batch."""
    import jax
    import jax.numpy as jnp
    m = dims(cfg)
    x = w["embed"][tokens]
    layers = {k: w[k] for k in ("ln", "in_proj", "conv_w", "conv_b",
                                "a_log", "dt_bias", "d_skip", "norm",
                                "out_proj")}
    body = jax.checkpoint(partial(_layer, cfg=cfg, m=m, mm_dtype=mm_dtype))
    x, _ = jax.lax.scan(lambda c, p: (body(c, p), None), x, layers)
    hN = _rms(x, w["ln_f"], cfg["norm_eps"])
    logits = _mm(hN, w["embed"].T, mm_dtype)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return jnp.mean(nll)


def lr_at(step: int, t: Dict) -> float:
    warm = min(step / max(t["warmup_steps"], 1), 1.0)
    frac = min(max((step - t["warmup_steps"])
                   / max(t["total_steps"] - t["warmup_steps"], 1), 0.0), 1.0)
    return t["learning_rate"] * warm * (0.1 + 0.45 * (1 + np.cos(np.pi
                                                                  * frac)))


def train(w, batches: List[Tuple[np.ndarray, np.ndarray]], cfg: Dict,
          mm_dtype: str = "float32", rows_per_pass: int = 2):
    """AdamW steps over ``batches`` from weights ``w``.  Gradients are
    taken ``rows_per_pass`` rows at a time and averaged (every row has the
    same number of tokens).  Returns (losses, first clipped gradient,
    weights after the last step), the last two as host arrays."""
    import jax
    import jax.numpy as jnp
    t = cfg["train"]
    b1, b2, eps = t["beta1"], t["beta2"], 1e-8
    grad_fn = jax.jit(jax.value_and_grad(
        partial(loss, cfg=cfg, mm_dtype=mm_dtype)))

    @jax.jit
    def adam(w, g, mo, v, step, lr):
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, t["grad_clip"]
                                                    / jnp.maximum(gn, 1e-9)),
                         g)
        mo = jax.tree.map(lambda m_, x: b1 * m_ + (1 - b1) * x, mo, g)
        v = jax.tree.map(lambda v_, x: b2 * v_ + (1 - b2) * x * x, v, g)
        bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
        w = {k: w[k] - lr * ((mo[k] / bc1) / (jnp.sqrt(v[k] / bc2) + eps)
                             + (t["weight_decay"] * w[k] if k in DECAYED
                                else 0.0))
             for k in w}
        return w, g, mo, v

    zeros = jax.tree.map(jnp.zeros_like, w)
    mo, v = zeros, jax.tree.map(jnp.zeros_like, w)
    losses, first = [], None
    for step, (tokens, labels) in enumerate(batches, start=1):
        n = tokens.shape[0]
        tot_l, tot_g = 0.0, None
        for r in range(0, n, rows_per_pass):
            lv, g = grad_fn(w, jnp.asarray(tokens[r:r + rows_per_pass]),
                            jnp.asarray(labels[r:r + rows_per_pass]))
            tot_l += float(lv)
            tot_g = g if tot_g is None else jax.tree.map(jnp.add, tot_g, g)
        passes = -(-n // rows_per_pass)
        g = jax.tree.map(lambda x: x / passes, tot_g)
        losses.append(tot_l / passes)
        w, g, mo, v = adam(w, g, mo, v, float(step), lr_at(step, t))
        if first is None:
            first = jax.device_get(g)
    return losses, first, jax.device_get(w)
