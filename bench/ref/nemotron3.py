"""Plain reference of Nemotron 3 Nano language-model training on one
chip's expert share (HF ``modeling_nemotron_h``).

Straightforward ``jax.numpy`` in float32 with full-precision matrix
products, in the benchmark's weight layout (``bench.gen.nemotron3``); it
imports nothing of the program.  Each layer is ``x = x + mixer(rms(x) *
ln)``, the mixer chosen by the layer's letter:

    M: z, xBC, dt = split(h @ in_proj)
       xBC = silu(causal depthwise conv(xBC, conv_w) + conv_b)
       xs, B, C = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(a_log)
       y = SSD(xs * dt, A * dt, B, C) + xs * D     (the Mamba-2 paper's
                                                     Listing 1, per head's group)
       out = (rms_per_group(y * silu(z)) * norm) @ out_proj
    *: causal softmax attention, 2 KV heads shared by 16 query heads each,
       scale 1/sqrt(head_dim), no rotary embedding, then @ wo
    E: s = sigmoid(h @ router);  the 6 best of s + router_bias over all
       experts;  weights 2.5 * s_sel / sum(s_sel);  each held expert
       keeps, within a row, the ceil(cf * T * 6 / E) of its tokens with the
       highest s + router_bias;  out = sum over kept held choices of
       weight * relu(h @ w_up)^2 @ w_down, plus relu(h @ ws_up)^2 @ ws_down

then logits = (rms(x) * ln_f) @ lm_head and the mean token cross entropy.
Each row of a batch is one routing group.  ``train`` takes AdamW steps
with global-norm clipping, bias correction and the linear-warmup cosine
schedule of the configuration's ``train`` block, weight decay on the
matrices.

``mm_dtype`` names the precision of the weight products' inputs; the
default float32 is the reference, ``float8_e4m3fn`` the lower-precision
control (inputs rounded to fp8, products accumulated in float32).
Attention scores are taken a block of queries at a time, so that a row
of 8192 positions fits a chip.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple

from bench.gen.nemotron3 import dims
from bench.ref.mamba2 import lr_at, ssd

# weight decay falls on the matrices, never on norms, biases or scalars
DECAYED = ("embed", "lm_head", "in_proj", "conv_w", "out_proj", "wq", "wk",
           "wv", "wo", "router", "w_up", "w_down", "ws_up", "ws_down")
QUERY_BLOCK = 512


def _mm(a, b, mm_dtype, spec="...d,df->...f"):
    import jax
    import jax.numpy as jnp
    if mm_dtype == "float32":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    dt = jnp.dtype(mm_dtype)
    return jnp.einsum(spec, a.astype(dt), b.astype(dt),
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def mamba(x, p, cfg, m, mm_dtype):
    import jax
    import jax.numpy as jnp
    eps = cfg["norm_eps"]
    b, s, _ = x.shape
    di, gn, h, g = m["di"], m["gn"], m["h"], cfg["n_groups"]
    zxbcdt = _mm(_rms(x, p["ln"], eps), p["in_proj"], mm_dtype)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * gn],
                  zxbcdt[..., 2 * di + 2 * gn:])
    k = m["k"]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + s] * p["conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :di].reshape(b, s, h, m["p"])
    B = jnp.repeat(xbc[..., di:di + gn].reshape(b, s, g, -1), h // g, 2)
    C = jnp.repeat(xbc[..., di + gn:].reshape(b, s, g, -1), h // g, 2)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["a_log"])
    y = ssd(xs * dt[..., None], A * dt, B, C, cfg["chunk_size"])
    y = (y + xs * p["d_skip"][:, None]).reshape(b, s, di)
    y = (y * jax.nn.silu(z)).reshape(b, s, g, di // g)
    y = _rms(y, p["norm"].reshape(g, -1), eps).reshape(b, s, di)
    return _mm(y, p["out_proj"], mm_dtype)


def attention(x, p, cfg, m, mm_dtype):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    b, s, _ = x.shape
    h = _rms(x, p["ln"], cfg["norm_eps"])
    q = _mm(h, p["wq"], mm_dtype, "bsd,dhk->bshk")
    rep = m["H"] // m["KV"]
    k = jnp.repeat(_mm(h, p["wk"], mm_dtype, "bsd,dhk->bshk"), rep, 2)
    v = jnp.repeat(_mm(h, p["wv"], mm_dtype, "bsd,dhk->bshk"), rep, 2)
    blk = min(QUERY_BLOCK, s)
    pos = jnp.arange(s)

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, 1)
        logits = jnp.einsum("bqhk,bshk->bhqs", qb, k,
                            precision=hi) / math.sqrt(m["hd"])
        causal = (start + jnp.arange(blk))[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), -1)
        return jnp.einsum("bhqs,bshk->bqhk", probs, v, precision=hi)

    out = jax.lax.map(one_block, jnp.arange(0, s, blk))    # (n, b, blk, ...)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, m["H"], m["hd"])
    return _mm(out, p["wo"], mm_dtype, "bshk,hkd->bsd")


def capacity(cfg: Dict, tokens: int) -> int:
    m = dims(cfg)
    c = math.ceil(cfg["moe_capacity_factor"] * tokens
                  * cfg["num_experts_per_tok"] / m["E"] - 1e-9)
    return max(1, min(c, tokens))


def routing(h, p, cfg, m, mm_dtype):
    """For a (rows, T, d) input: each held expert's weight for each token
    (0 where the expert was not chosen or dropped the token), (rows, T,
    E_l), and the held experts' assignments and drops."""
    import jax
    import jax.numpy as jnp
    b, T, _ = h.shape
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm(h, p["router"], mm_dtype))      # (b, T, E)
    sel = s + p["router_bias"]
    _, top = jax.lax.top_k(sel, k)
    chosen = jnp.any(top[..., :, None] == jnp.arange(m["E"]), -2)
    ws = jnp.where(chosen, s, 0.0)
    ws = cfg["routed_scaling_factor"] * ws / ws.sum(-1, keepdims=True)
    held = slice(m["off"], m["off"] + m["El"])
    chosen, prio, ws = chosen[..., held], sel[..., held], ws[..., held]
    # within a row, an expert keeps its best `capacity` tokens; equal
    # priorities go to the earlier token
    order = jnp.argsort(jnp.where(chosen, -prio, jnp.inf), axis=1,
                        stable=True)
    rank = jnp.argsort(order, axis=1)
    keep = chosen & (rank < capacity(cfg, T))
    return (jnp.where(keep, ws, 0.0), jnp.sum(chosen),
            jnp.sum(chosen) - jnp.sum(keep))


def experts(x, p, cfg, m, mm_dtype):
    import jax
    import jax.numpy as jnp
    h = _rms(x, p["ln"], cfg["norm_eps"])
    w, _, _ = routing(h, p, cfg, m, mm_dtype)

    def relu2(t):
        return jnp.square(jax.nn.relu(t))

    up = _mm(h, p["w_up"], mm_dtype, "bsd,edf->ebsf")
    y = _mm(relu2(up), p["w_down"], mm_dtype, "ebsf,efd->ebsd")
    out = jnp.einsum("ebsd,bse->bsd", y, w,
                     precision=jax.lax.Precision.HIGHEST)
    return out + _mm(relu2(_mm(h, p["ws_up"], mm_dtype)), p["ws_down"],
                     mm_dtype)


MIXERS = {"M": mamba, "*": attention, "E": experts}


def loss(w, tokens, labels, cfg: Dict, mm_dtype: str = "float32"):
    """Mean token cross entropy of one batch."""
    import jax
    import jax.numpy as jnp
    m = dims(cfg)
    x = w["embed"][tokens]
    for c, p in zip(m["pattern"], w["layers"]):
        mixer = jax.checkpoint(partial(MIXERS[c], cfg=cfg, m=m,
                                       mm_dtype=mm_dtype))
        x = x + mixer(x, p)
    logits = _mm(_rms(x, w["ln_f"], cfg["norm_eps"]), w["lm_head"], mm_dtype)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return jnp.mean(nll)


def expert_counts(w, tokens, cfg: Dict) -> Tuple[int, int]:
    """(assigned, dropped) of the held experts over a batch's rows, by
    the reference's rule."""
    import jax
    m = dims(cfg)

    @jax.jit
    def count(w, tokens):
        x = w["embed"][tokens]
        got = []
        for c, p in zip(m["pattern"], w["layers"]):
            if c == "E":
                h = _rms(x, p["ln"], cfg["norm_eps"])
                got.append(routing(h, p, cfg, m, "float32")[1:])
            x = x + MIXERS[c](x, p, cfg, m, "float32")
        return sum(a for a, _ in got), sum(d for _, d in got)

    a, d = count(w, tokens)
    return int(a), int(d)


def _decayed(path) -> bool:
    return getattr(path[-1], "key", None) in DECAYED


def train(w, batches: List[Tuple], cfg: Dict, mm_dtype: str = "float32",
          rows_per_pass: int = 1):
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
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=0)

    @partial(jax.jit, donate_argnums=(0, 2, 3))
    def adam(w, g, mo, v, step, lr, passes):
        g = jax.tree.map(lambda x: x / passes, g)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, t["grad_clip"]
                                                    / jnp.maximum(gn, 1e-9)),
                         g)
        mo = jax.tree.map(lambda m_, x: b1 * m_ + (1 - b1) * x, mo, g)
        v = jax.tree.map(lambda v_, x: b2 * v_ + (1 - b2) * x * x, v, g)
        bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step

        def upd(path, w_, m_, v_):
            decay = t["weight_decay"] * w_ if _decayed(path) else 0.0
            return w_ - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps) + decay)

        return jax.tree_util.tree_map_with_path(upd, w, mo, v), g, mo, v

    mo = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for step, (tokens, labels) in enumerate(batches, start=1):
            n = tokens.shape[0]
            tot_l, tot_g = 0.0, None
            for r in range(0, n, rows_per_pass):
                lv, g = grad_fn(w, jnp.asarray(tokens[r:r + rows_per_pass]),
                                jnp.asarray(labels[r:r + rows_per_pass]))
                tot_l += float(lv)
                tot_g = g if tot_g is None else add(tot_g, g)
                del g
            passes = -(-n // rows_per_pass)
            losses.append(tot_l / passes)
            w, g, mo, v = adam(w, tot_g, mo, v, float(step), lr_at(step, t),
                               float(passes))
            del tot_g
            if first is None:
                first = jax.device_get(g)
            del g
    return losses, first, jax.device_get(w)
