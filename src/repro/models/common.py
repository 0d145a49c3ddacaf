"""Shared model building blocks: norms, activations, RoPE, init, sharding.

Everything is functional: params are plain pytrees of jnp arrays, layers are
pure functions.  Activation sharding constraints are applied through a
context-managed ``AxisRules`` so the same model code runs unconstrained on a
single CPU device (smoke tests) and fully sharded under the production mesh
(dry-run / training).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# --------------------------------------------------------------------------
# Activation-sharding rules (t5x-style logical axes, minimal version)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Logical-axis -> mesh-axis rules for activation constraints."""

    batch: Tuple[str, ...] = ()        # e.g. ('pod', 'data')
    heads: Optional[str] = None        # e.g. 'model'
    ff: Optional[str] = None           # e.g. 'model'
    vocab: Optional[str] = None        # e.g. 'model'
    # 'model', or ('data','model') in the serving layout (1 expert/chip)
    expert: object = None
    seq: Optional[str] = None          # sequence parallelism (hillclimb knob)
    enabled: bool = False


_STATE = threading.local()


def current_rules() -> AxisRules:
    return getattr(_STATE, "rules", AxisRules())


@contextlib.contextmanager
def axis_rules(rules: AxisRules):
    prev = current_rules()
    _STATE.rules = rules
    try:
        yield
    finally:
        _STATE.rules = prev


def _constrain(x: jax.Array, spec: P) -> jax.Array:
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        # outside a mesh context (e.g. eager smoke test) -> no-op
        return x


def shard_batch_seq(x: jax.Array) -> jax.Array:
    """Constrain (batch, seq, ...) activations: batch->DP, optionally seq->SP."""
    r = current_rules()
    if not r.enabled:
        return x
    batch = r.batch if r.batch else None
    spec = [batch, r.seq] + [None] * (x.ndim - 2)
    return _constrain(x, P(*spec))


def shard_heads(x: jax.Array) -> jax.Array:
    """Constrain (batch, seq, heads, head_dim) activations: heads->TP."""
    r = current_rules()
    if not r.enabled:
        return x
    batch = r.batch if r.batch else None
    return _constrain(x, P(batch, None, r.heads, None))


def shard_ff(x: jax.Array) -> jax.Array:
    """Constrain (batch, seq, d_ff) activations: hidden->TP."""
    r = current_rules()
    if not r.enabled:
        return x
    batch = r.batch if r.batch else None
    spec = [batch] + [None] * (x.ndim - 2) + [r.ff]
    return _constrain(x, P(*spec))


def shard_vocab(x: jax.Array) -> jax.Array:
    r = current_rules()
    if not r.enabled:
        return x
    batch = r.batch if r.batch else None
    spec = [batch] + [None] * (x.ndim - 2) + [r.vocab]
    return _constrain(x, P(*spec))


# --------------------------------------------------------------------------
# Norms / activations
# --------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float, *,
             zero_centered: bool = False) -> jax.Array:
    """RMSNorm in fp32 with cast back (gemma uses zero-centered scale)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    w = scale.astype(jnp.float32)
    if zero_centered:
        w = 1.0 + w
    return (y * w).astype(dtype)


def group_rms_norm(x: jax.Array, scale: jax.Array, eps: float,
                   groups: int) -> jax.Array:
    """RMSNorm over ``groups`` equal slices of the last axis, each with its
    own mean square (mamba_ssm's ``RMSNormGated`` with ``group_size =
    width / groups``); one group is ``rms_norm`` over the whole width."""
    y = rms_norm(x.reshape(*x.shape[:-1], groups, -1),
                 scale.reshape(groups, -1), eps)
    return y.reshape(x.shape)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "relu2":              # squared ReLU (Primer; Nemotron-H)
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name!r}")


def softcap(logits: jax.Array, cap: float) -> jax.Array:
    """Gemma2 logit soft-capping: cap * tanh(x / cap)."""
    if cap <= 0.0:
        return logits
    return cap * jnp.tanh(logits / cap)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float, fraction: float = 1.0,
                     dtype=jnp.float32) -> jax.Array:
    """Inverse frequencies for the rotated sub-dimension."""
    rot_dim = int(head_dim * fraction)
    rot_dim -= rot_dim % 2
    exponent = jnp.arange(0, rot_dim, 2, dtype=dtype) / rot_dim
    return 1.0 / (theta ** exponent)       # (rot_dim // 2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               fraction: float = 1.0) -> jax.Array:
    """Apply rotary embedding.

    x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).
    With fraction < 1 only the leading ``fraction`` of head_dim is rotated
    (ChatGLM 2d-RoPE).
    """
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta, fraction)
    rot_dim = inv_freq.shape[0] * 2
    x_rot, x_pass = x[..., :rot_dim], x[..., rot_dim:]

    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (..., seq, rot/2)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]

    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    rotated = jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)
    if rot_dim == head_dim:
        return rotated
    return jnp.concatenate([rotated, x_pass], axis=-1)


# --------------------------------------------------------------------------
# Initializers
# --------------------------------------------------------------------------

def dense_init(key, shape: Sequence[int], in_axis: int = 0, dtype=jnp.float32):
    """Truncated-normal fan-in init, stored in fp32 (cast at use)."""
    fan_in = shape[in_axis]
    std = fan_in ** -0.5
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, tuple(shape),
                                              jnp.float32)).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    # fan-in scale keeps tied-embedding logits O(1); archs with
    # embed_scale (gemma) recover O(1) inputs via the sqrt(d) multiplier.
    std = shape[-1] ** -0.5
    return (std * jax.random.normal(key, tuple(shape), jnp.float32)).astype(dtype)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))
