"""Model facade: init / train / prefill / decode for every assigned arch.

Public API:
  init_params(key, cfg, ...)        -> param pytree
  forward_train(params, batch, cfg) -> (logits, aux, hidden)
  loss_fn(params, batch, cfg)       -> (loss, metrics)
  init_decode_state(cfg, batch, max_len)  -> cache pytree
  prefill(params, batch, cfg, cache)      -> (logits, cache)
  decode_step(params, token, position, cfg, cache) -> (logits, cache)

Batches are dicts: tokens/labels (+ frames for audio, image_embeds for vlm —
the modality frontends are stubs per the assignment; embeddings arrive
precomputed).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common, moe as moe_lib, transformer as tfm
from repro.models.common import dense_init, embed_init, shard_batch_seq
from repro.models.transformer import (ENCODER, apply_norm, init_block,
                                      init_norm, init_stack,
                                      init_stack_cache, sinusoid_positions,
                                      stack_forward_decode,
                                      stack_forward_prefill,
                                      stack_forward_train)

MTP_LOSS_WEIGHT = 0.3
# metrics a train step sums over its microbatches (the others are means)
MOE_COUNTERS = ("moe_assigned", "moe_dropped")
ATTN_COUNTERS = ("attn_chunks_run", "attn_chunks_all")
STEP_COUNTERS = MOE_COUNTERS + ATTN_COUNTERS


def _encoder_cfg(cfg: ModelConfig) -> ModelConfig:
    return cfg.scaled(n_layers=cfg.n_encoder_layers,
                      attn_pattern=(ENCODER,), n_experts=0,
                      n_dense_layers=0, is_encoder_decoder=False)


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig, dtype=jnp.float32,
                scan_layers: bool = True) -> Dict:
    ks = common.split_keys(key, 8)
    params: Dict[str, Any] = {
        "embed": embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype),
        "decoder": init_stack(ks[1], cfg, dtype, scan_layers),
        "ln_f": init_norm(cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], (cfg.d_model, cfg.vocab_size),
                                       dtype=dtype)
    if cfg.is_encoder_decoder:
        ecfg = _encoder_cfg(cfg)
        params["encoder"] = {
            "stack": init_stack(ks[3], ecfg, dtype, scan_layers),
            "ln_f": init_norm(ecfg, dtype),
        }
    if cfg.mtp_depth > 0:
        params["mtp"] = {
            "proj": dense_init(ks[4], (2 * cfg.d_model, cfg.d_model), dtype=dtype),
            "norm_h": init_norm(cfg, dtype),
            "norm_e": init_norm(cfg, dtype),
            "block": init_block(ks[5], cfg, cfg.attn_pattern[0],
                                cfg.n_layers, dtype),
            "ln_f": init_norm(cfg, dtype),
        }
    return params


# --------------------------------------------------------------------------
# Shared pieces
# --------------------------------------------------------------------------

def _embed(params, tokens: jax.Array, cfg: ModelConfig,
           compute_dtype) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), compute_dtype)
    return shard_batch_seq(x)


def _logits(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Final norm -> head -> softcap -> pad-vocab mask.  fp32 out."""
    h = apply_norm(params["ln_f"], x, cfg)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(h.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(h.dtype))
    logits = common.shard_vocab(logits).astype(jnp.float32)
    logits = common.softcap(logits, cfg.final_softcap)
    if cfg.vocab_real != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.vocab_size) < cfg.vocab_real
        logits = jnp.where(pad_mask, logits, -1e30)
    return logits


def _encode(params, frames: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Whisper encoder over precomputed frame embeddings (stub frontend)."""
    ecfg = _encoder_cfg(cfg)
    x = frames.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    x = x + sinusoid_positions(x.shape[1], cfg.d_model, x.dtype)[None]
    x, _ = stack_forward_train(params["encoder"]["stack"], x, ecfg,
                               positions=jnp.arange(x.shape[1])[None])
    return apply_norm(params["encoder"]["ln_f"], x, ecfg)


def _memory(params, batch: Dict, cfg: ModelConfig) -> Optional[jax.Array]:
    if cfg.is_encoder_decoder:
        return _encode(params, batch["frames"], cfg)
    if cfg.cross_attn_period:
        return batch["image_embeds"].astype(
            jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    return None


def _compute_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# --------------------------------------------------------------------------
# Train forward + loss
# --------------------------------------------------------------------------

def forward_train(params, batch: Dict, cfg: ModelConfig, *,
                  remat: str = "none", moe_dense_oracle: bool = False
                  ) -> Tuple[jax.Array, Dict, jax.Array]:
    """-> (logits fp32, aux, final_hidden); aux holds the load-balance
    loss ``balance``, the expert layers' counters and the chunked
    attention's, summed over blocks."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    cdt = _compute_dtype(cfg)
    x = _embed(params, tokens, cfg, cdt)
    if cfg.pos_embedding == "sinusoid":
        x = x + sinusoid_positions(s, cfg.d_model, cdt)[None]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    memory = _memory(params, batch, cfg)
    x, aux = stack_forward_train(params["decoder"], x, cfg,
                                 positions=positions, memory=memory,
                                 remat=remat,
                                 moe_dense_oracle=moe_dense_oracle)
    return _logits(params, x, cfg), aux, x


def _ce(logits: jax.Array, labels: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Masked token cross entropy; labels < 0 are ignored."""
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    return (nll * mask).sum() / denom, denom


def _mtp_loss(params, batch, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    """DeepSeek multi-token prediction: predict t+2 from [h_t; emb(t+1)]."""
    tokens, labels = batch["tokens"], batch["labels"]
    b, s = tokens.shape
    cdt = hidden.dtype
    p = params["mtp"]
    # shift: combine hidden at t with the embedding of token t+1
    h = apply_norm(p["norm_h"], hidden[:, :-1], cfg)
    e = apply_norm(p["norm_e"],
                   _embed(params, tokens[:, 1:], cfg, cdt), cfg)
    merged = jnp.einsum("bsd,dm->bsm",
                        jnp.concatenate([h, e], axis=-1),
                        p["proj"].astype(cdt))
    positions = jnp.broadcast_to(jnp.arange(s - 1, dtype=jnp.int32)[None],
                                 (b, s - 1))
    merged, _, _ = tfm.block_forward(p["block"], merged, cfg,
                                     cfg.attn_pattern[0], mode="train",
                                     positions=positions)
    logits = _logits({**params, "ln_f": p["ln_f"]}, merged, cfg)
    mtp_labels = jnp.pad(labels[:, 1:], ((0, 0), (0, 0)))  # labels already t+1
    # predicting token t+2 == label at position t+1
    loss, _ = _ce(logits, mtp_labels)
    return loss


def loss_fn(params, batch: Dict, cfg: ModelConfig, *,
            remat: str = "none") -> Tuple[jax.Array, Dict]:
    logits, aux, hidden = forward_train(params, batch, cfg, remat=remat)
    ce, n_tok = _ce(logits, batch["labels"])
    loss = ce + cfg.router_aux_coef * aux["balance"]
    metrics = {"ce": ce, "aux": aux["balance"], "tokens": n_tok}
    counters = STEP_COUNTERS if cfg.is_moe else ATTN_COUNTERS
    metrics.update({k: aux[k] for k in counters})
    if cfg.mtp_depth > 0:
        mtp = _mtp_loss(params, batch, cfg, hidden)
        loss = loss + MTP_LOSS_WEIGHT * mtp
        metrics["mtp"] = mtp
    metrics["loss"] = loss
    return loss, metrics


# --------------------------------------------------------------------------
# Serving: prefill + decode
# --------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      scan_layers: bool = True, dtype=jnp.bfloat16) -> Dict:
    return init_stack_cache(cfg, batch, max_len, scan_layers, dtype)


def prefill(params, batch: Dict, cfg: ModelConfig, cache: Dict
            ) -> Tuple[jax.Array, Dict]:
    """Process the prompt; returns (last-token logits fp32, filled cache)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    cdt = _compute_dtype(cfg)
    x = _embed(params, tokens, cfg, cdt)
    if cfg.pos_embedding == "sinusoid":
        x = x + sinusoid_positions(s, cfg.d_model, cdt)[None]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    memory = _memory(params, batch, cfg)
    x, cache = stack_forward_prefill(params["decoder"], cache, x, cfg,
                                     positions=positions, memory=memory)
    logits = _logits(params, x[:, -1:], cfg)
    return logits[:, 0], cache


def decode_step(params, token: jax.Array, position: jax.Array,
                cfg: ModelConfig, cache: Dict) -> Tuple[jax.Array, Dict]:
    """One token for the whole batch.  token: (b, 1) int32; position scalar."""
    cdt = _compute_dtype(cfg)
    x = _embed(params, token, cfg, cdt)
    if cfg.pos_embedding == "sinusoid":
        table = sinusoid_positions(1, cfg.d_model, cdt)  # pos encoded rel. 0
        # use absolute position: recompute the sinusoid row at `position`
        dim = jnp.arange(cfg.d_model // 2, dtype=jnp.float32)
        inv = jnp.exp(-math.log(10000.0) * dim / max(cfg.d_model // 2 - 1, 1))
        ang = position.astype(jnp.float32) * inv
        row = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)]).astype(cdt)
        x = x + row[None, None, :]
        del table
    x, cache = stack_forward_decode(params["decoder"], cache, x, cfg,
                                    position=position)
    logits = _logits(params, x, cfg)
    return logits[:, 0], cache


# --------------------------------------------------------------------------
# Analytic parameter counts (roofline)
# --------------------------------------------------------------------------

def count_params_analytic(cfg: ModelConfig, active_only: bool = False,
                          exclude_embed: bool = False) -> int:
    shapes = jax.eval_shape(
        lambda key: init_params(key, cfg), jax.random.key(0))
    total = sum(_numel(l.shape) for l in jax.tree.leaves(shapes))
    if exclude_embed:
        total -= cfg.vocab_size * cfg.d_model
    if active_only and cfg.is_moe:
        # expert layers, stacked or not: each has one w_up of E_l experts
        expert = cfg.d_model * cfg.d_expert
        n_moe = sum(
            _numel(leaf.shape) // (cfg.held_experts * expert)
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]
            if getattr(path[-1], "key", None) == "w_up")
        per_expert = (3 if cfg.gated_mlp else 2) * expert
        total -= n_moe * per_expert * (cfg.held_experts - cfg.top_k)
    return total


def _numel(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


# --------------------------------------------------------------------------
# Synthetic batches (tests / examples / dry-run shapes)
# --------------------------------------------------------------------------

def batch_struct(cfg: ModelConfig, batch: int, seq: int) -> Dict:
    """ShapeDtypeStructs for one training batch (no allocation)."""
    d: Dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    if cfg.is_encoder_decoder:
        d["frames"] = jax.ShapeDtypeStruct(
            (batch, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    if cfg.cross_attn_period:
        d["image_embeds"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
    return d


def make_batch(key, cfg: ModelConfig, batch: int, seq: int) -> Dict:
    ks = common.split_keys(key, 3)
    toks = jax.random.randint(ks[0], (batch, seq), 0, cfg.vocab_real)
    d = {"tokens": toks,
         "labels": jnp.concatenate(
             [toks[:, 1:], jnp.full((batch, 1), -1, toks.dtype)], axis=1)}
    if cfg.is_encoder_decoder:
        d["frames"] = jax.random.normal(
            ks[1], (batch, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    if cfg.cross_attn_period:
        d["image_embeds"] = jax.random.normal(
            ks[2], (batch, cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
    return d
