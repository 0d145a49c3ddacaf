"""jit-able train / prefill / decode steps used by launchers and dry-run."""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.models import model as mdl
from repro.optim import adamw_update, AdamWState


def make_train_step(cfg: ModelConfig, run: RunConfig):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def compute_grads(params, batch):
        def lw(p):
            return mdl.loss_fn(p, batch, cfg, remat=run.remat)
        (loss, metrics), grads = jax.value_and_grad(lw, has_aux=True)(params)
        return grads, metrics

    def accum_grads(params, batch):
        """Gradient accumulation over microbatches via scan."""
        n = run.microbatch
        mb = jax.tree.map(
            lambda x: x.reshape(n, x.shape[0] // n, *x.shape[1:]), batch)

        def body(acc, mbatch):
            grads, metrics = compute_grads(params, mbatch)
            acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / n, acc, grads)
            return acc, metrics

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        grads, ms = jax.lax.scan(body, zeros, mb)
        metrics = {k: (jnp.sum if k in mdl.STEP_COUNTERS else jnp.mean)(v)
                   for k, v in ms.items()}
        return grads, metrics

    def train_step(params, opt_state: AdamWState, batch: Dict):
        if run.microbatch > 1:
            grads, metrics = accum_grads(params, batch)
        else:
            grads, metrics = compute_grads(params, batch)
        params, opt_state, om = adamw_update(params, grads, opt_state, run)
        metrics = dict(metrics)
        metrics.update(om)
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig):
    """(params, batch, cache) -> (last-token logits, cache)."""

    def prefill_step(params, batch, cache):
        return mdl.prefill(params, batch, cfg, cache)

    return prefill_step


def make_decode_step(cfg: ModelConfig):
    """(params, cache, token, position) -> (next_token, cache).

    One new token for the whole batch against a filled KV/state cache —
    this is what the decode_32k / long_500k cells lower.
    """

    def serve_step(params, cache, token, position):
        logits, cache = mdl.decode_step(params, token, position, cfg, cache)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return nxt, cache

    return serve_step


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch):
        loss, metrics = mdl.loss_fn(params, batch, cfg)
        return metrics

    return eval_step
