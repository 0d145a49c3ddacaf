"""Mixture-of-Experts block: the expert share, with work fixed by shapes.

One dispatch serves every MoE configuration.  The layer is told which
experts this chip holds (``expert_offset``, ``n_local_experts`` of the
``n_experts`` the router scores; all of them by default) and routes every
token over all experts; it computes the part of the result that the held
experts give, plus the shared expert.  What experts held elsewhere would
add is left out: under expert parallelism another chip computes it.

Capacity: each held expert has a buffer of ``C = ceil(cf * T * k / E)``
rows (at most ``T``), with ``T`` the tokens of a routing group (a batch
row; single-token decode routes the batch as one group) and ``cf`` the
capacity factor.  An expert with more than ``C`` assignments keeps the
``C`` of highest router priority (the selection score); the rest are
dropped, as in GShard and Switch.  Tests use ``cf = E/k``, which makes
the dispatch lossless, and compare with the dense oracle below.

Routing maps: one stable sort of the group's ``T*k`` assignments by
(held expert, -priority) lays them out expert after expert, best first.
From it come two fixed-size integer maps: slot -> assignment (``E_l*C``
slots) and assignment -> slot (a second sort returns the slots to
assignment order).  An empty slot, and a dropped or unheld assignment,
point at one zero row past the data.  Dispatch, combine and the routing
weights move by gathers along these maps; each gather's gradient is the
gather along the other map (``_gather_sum``'s ``custom_vjp``), so the
layer has no scatter forward or backward, and its device work and index
pattern depend on the shapes alone, not on how tokens route.  The expert
matmuls are dense einsums over ``(E_l, C, d)``.

Counters: ``moe_assigned`` counts assignments to held experts,
``moe_dropped`` those of them over capacity.

Expert-parallel sharding: the (G, E_l, C, d) dispatch tensor and the
expert weights shard E_l over the 'model' axis.

Routers: softmax (qwen2-moe, + load-balance aux loss) and sigmoid with
aux-loss-free bias balancing (deepseek-v3, nemotron-h: the bias only
affects top-k *selection*, gates use the raw sigmoid scores).  The
selected gates are normalised to sum to one and scaled by
``routed_scaling_factor``.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.common import activation, dense_init

SCOPE = "sage_moe"          # the expert layer's named scope in the HLO


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_expert
    el = cfg.held_experts
    ks = common.split_keys(key, 8)
    p = {
        "router": dense_init(ks[0], (d, e), dtype=jnp.float32),
        "w_up": dense_init(ks[2], (el, d, f), in_axis=1, dtype=dtype),
        "w_down": dense_init(ks[3], (el, f, d), in_axis=1, dtype=dtype),
    }
    if cfg.gated_mlp:
        p["w_gate"] = dense_init(ks[1], (el, d, f), in_axis=1, dtype=dtype)
    if cfg.router_aux_free_bias:
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
    if cfg.n_shared_experts:
        fs = cfg.d_shared_expert
        p["ws_up"] = dense_init(ks[5], (d, fs), dtype=dtype)
        p["ws_down"] = dense_init(ks[6], (fs, d), dtype=dtype)
        if cfg.gated_mlp:
            p["ws_gate"] = dense_init(ks[4], (d, fs), dtype=dtype)
        if cfg.shared_expert_gate:
            p["shared_gate"] = dense_init(ks[7], (d, 1), dtype=dtype)
    return p


def router_scores(p: Dict, x: jax.Array, cfg: ModelConfig
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (gates (..., E) fp32, selection_scores (..., E), logits)."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"])
    if cfg.router_type == "sigmoid":
        gates = jax.nn.sigmoid(logits)
        sel = gates + (p["router_bias"] if "router_bias" in p else 0.0)
    else:
        gates = jax.nn.softmax(logits, axis=-1)
        sel = gates
    return gates, sel, logits


def load_balance_loss(gates: jax.Array, topk_mask: jax.Array, k: int) -> jax.Array:
    """Switch-style aux loss: E * sum_e f_e * P_e (fp32 scalar)."""
    e = gates.shape[-1]
    axes = tuple(range(topk_mask.ndim - 1))
    f = jnp.mean(topk_mask.astype(jnp.float32), axis=axes)
    pr = jnp.mean(gates, axis=axes)
    return e * jnp.sum(f * pr) / k


def routing_weights(gates: jax.Array, onehot: jax.Array,
                    cfg: ModelConfig) -> jax.Array:
    """Weights of each token's k choices: the selected gates normalised to
    sum to one, times ``routed_scaling_factor``.  gates (..., E), onehot
    (..., k, E) -> (..., k); a contraction, so no scatter in the gradient."""
    g = jnp.einsum("...e,...ke->...k", gates, onehot)
    return (cfg.routed_scaling_factor * g
            / jnp.maximum(g.sum(-1, keepdims=True), 1e-9))


def capacity(cfg: ModelConfig, tokens: int) -> int:
    """Rows of each held expert's buffer: ceil(cf * T * k / E), 1 to T."""
    c = math.ceil(cfg.moe_capacity_factor * tokens * cfg.top_k
                  / cfg.n_experts - 1e-9)
    return max(1, min(c, tokens))


def _shard_dispatch(t: jax.Array) -> jax.Array:
    """Constrain (G, E, C, d) dispatch tensors: G->batch, E->expert axis.

    In the serving layout the expert axis is ('data','model'); the group
    dim then stays unsharded (it is 1 in decode) so no mesh axis repeats.
    """
    r = common.current_rules()
    if not r.enabled:
        return t
    from jax.sharding import PartitionSpec as P
    batch = r.batch if r.batch else None
    expert_axes = (r.expert if isinstance(r.expert, tuple)
                   else ((r.expert,) if r.expert else ()))
    if batch and any(a in expert_axes for a in batch):
        batch = tuple(a for a in batch if a not in expert_axes) or None
    try:
        return jax.lax.with_sharding_constraint(
            t, P(batch, r.expert, *([None] * (t.ndim - 2))))
    except (ValueError, RuntimeError):
        return t


# --------------------------------------------------------------------------
# Routing maps and the gathers along them
# --------------------------------------------------------------------------

def routing_maps(sel: jax.Array, k: int, held: int, offset: int, cap: int):
    """One routing group's maps.  sel: (T, E) selection scores.

    Returns (top_e (T, k) chosen experts, slot_asg (held*cap,) the
    assignment ``t*k + j`` in each slot or ``T*k`` where empty, asg_slot
    (T*k,) the slot of each assignment or ``held*cap`` where it is
    dropped or its expert is not held, assigned, dropped)."""
    T = sel.shape[0]
    n = T * k
    top_sel, top_e = jax.lax.top_k(sel, k)                  # (T, k)
    local = top_e - offset
    is_held = (local >= 0) & (local < held)
    key_e = jnp.where(is_held, local, held).reshape(n).astype(jnp.int32)
    asg = jnp.arange(n, dtype=jnp.int32)
    # stable: equal priorities keep token order
    e_sorted, _, asg_sorted = jax.lax.sort(
        (key_e, -top_sel.reshape(n), asg), num_keys=2, is_stable=True)
    counts = jnp.sum(key_e[:, None] == jnp.arange(held)[None, :], axis=0,
                     dtype=jnp.int32)                        # (held,)
    start = jnp.cumsum(counts) - counts
    # slot (e, c) holds the c-th best assignment of expert e
    c = jnp.arange(cap, dtype=jnp.int32)
    pos = start[:, None] + c[None, :]
    slot_asg = jnp.where(c[None, :] < counts[:, None],
                         asg_sorted[jnp.minimum(pos, n - 1)], n).reshape(-1)
    # each sorted assignment's slot, then back to assignment order
    start_p = jnp.concatenate([start, jnp.zeros((1,), jnp.int32)])
    rank = jnp.arange(n, dtype=jnp.int32) - start_p[e_sorted]
    kept = (e_sorted < held) & (rank < cap)
    slot_sorted = jnp.where(kept, e_sorted * cap + rank, held * cap)
    _, asg_slot = jax.lax.sort((asg_sorted, slot_sorted), num_keys=1)
    assigned = jnp.sum(counts)
    dropped = assigned - jnp.sum(kept)
    return top_e, slot_asg, asg_slot, assigned, dropped


@jax.custom_vjp
def _gather_sum(x: jax.Array, idx: jax.Array, inv: jax.Array) -> jax.Array:
    """out[m] = sum_r x[idx[m, r]], where index ``len(x)`` reads a zero row.

    ``inv`` is the same matching seen from the other side: out row m and
    x row i are paired (once) iff idx[m] holds i and inv[i] holds m.  The
    gradient is then ``_gather_sum(g, inv, idx)``: a gather, never a
    scatter.  x: (N, d); idx: (M, R); inv: (N, R')."""
    xp = jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)])
    return jnp.sum(xp[idx], axis=1)


def _gather_sum_fwd(x, idx, inv):
    return _gather_sum(x, idx, inv), (idx, inv)


def _gather_sum_bwd(res, g):
    idx, inv = res
    with jax.named_scope(SCOPE):
        return _gather_sum(g, inv, idx), None, None


_gather_sum.defvjp(_gather_sum_fwd, _gather_sum_bwd)


def _group_dispatch(xg, sel, gates, cfg: ModelConfig, cap: int):
    """One group: tokens into the held experts' buffers.  xg (T, d) ->
    (xd (held*cap, d), combine(yd) -> (T, d), weight of each slot,
    topk mask (T, E), assigned, dropped)."""
    T = xg.shape[0]
    k, held = cfg.top_k, cfg.held_experts
    top_e, slot_asg, asg_slot, assigned, dropped = routing_maps(
        jax.lax.stop_gradient(sel), k, held, cfg.expert_offset, cap)
    onehot = jax.nn.one_hot(top_e, cfg.n_experts, dtype=jnp.float32)
    w = routing_weights(gates, onehot, cfg).reshape(T * k, 1)
    slot_tok = (slot_asg // k)[:, None]                     # T: zero row
    w_slot = _gather_sum(w, slot_asg[:, None], asg_slot[:, None])[:, 0]
    xd = _gather_sum(xg, slot_tok, asg_slot.reshape(T, k))
    return (xd, w_slot, asg_slot.reshape(T, k), slot_tok,
            onehot.sum(-2), assigned, dropped)


def _expert_ffn(p: Dict, x: jax.Array, cfg: ModelConfig, *,
                shared: bool) -> jax.Array:
    """The experts' MLP: act(x W_up) [* (x W_gate)] W_down, per expert
    (x (..., E_l, C, d)) or for the shared expert (x (..., d))."""
    act = activation(cfg.act)
    dt = x.dtype
    if shared:
        up = x @ p["ws_up"].astype(dt)
        h = (act(x @ p["ws_gate"].astype(dt)) * up if "ws_gate" in p
             else act(up))
        return common.shard_ff(h) @ p["ws_down"].astype(dt)
    up = jnp.einsum("...ecd,edf->...ecf", x, p["w_up"].astype(dt))
    if "w_gate" in p:
        h = act(jnp.einsum("...ecd,edf->...ecf", x,
                           p["w_gate"].astype(dt))) * up
    else:
        h = act(up)
    return jnp.einsum("...ecf,efd->...ecd", h, p["w_down"].astype(dt))


def _shared(p: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xt = x.reshape(-1, x.shape[-1])
    ys = _expert_ffn(p, xt, cfg, shared=True)
    if cfg.shared_expert_gate:
        ys = ys * jax.nn.sigmoid(xt @ p["shared_gate"].astype(x.dtype))
    return ys.reshape(x.shape)


def moe_block(p: Dict, x: jax.Array, cfg: ModelConfig
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The expert share.  x: (b, s, d) -> (out, aux): aux holds the
    load-balance loss ``balance`` and the counters ``moe_assigned`` and
    ``moe_dropped``."""
    with jax.named_scope(SCOPE):
        b, s, d = x.shape
        held = cfg.held_experts
        # group per batch row; single-token decode: one group over the batch
        g, gs = (b, s) if s > 1 else (1, b)
        xg = x.reshape(g, gs, d)
        cap = capacity(cfg, gs)

        gates, sel, _ = router_scores(p, xg, cfg)            # (G, T, E)
        xd, w_slot, asg_slot, slot_tok, mask, assigned, dropped = jax.vmap(
            lambda xg_, sel_, gates_: _group_dispatch(xg_, sel_, gates_, cfg,
                                                      cap))(xg, sel, gates)
        aux = load_balance_loss(gates, mask, cfg.top_k)

        xd = _shard_dispatch(xd.reshape(g, held, cap, d))
        yd = _shard_dispatch(_expert_ffn(p, xd, cfg, shared=False))
        yd = yd.reshape(g, held * cap, d) * w_slot[..., None].astype(x.dtype)
        out = jax.vmap(_gather_sum)(yd, asg_slot, slot_tok).reshape(b, s, d)
        if cfg.n_shared_experts:
            out = out + _shared(p, x, cfg)
    return out, {"balance": aux, "moe_assigned": jnp.sum(assigned),
                 "moe_dropped": jnp.sum(dropped)}


# --------------------------------------------------------------------------
# Dense oracle (tests): exact per-token top-k expert computation
# --------------------------------------------------------------------------

def moe_block_dense(p: Dict, x: jax.Array, cfg: ModelConfig
                    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Compute every held expert for every token, combine the top-k of
    them that are held.  O(E_l) compute, no capacity."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    gates, sel, _ = router_scores(p, xt, cfg)
    _, top_e = jax.lax.top_k(sel, cfg.top_k)
    onehot = jax.nn.one_hot(top_e, cfg.n_experts, dtype=jnp.float32)
    aux = load_balance_loss(gates, onehot.sum(-2), cfg.top_k)
    w = jnp.einsum("nk,nke->ne", routing_weights(gates, onehot, cfg), onehot)
    w = w[:, cfg.expert_offset:cfg.expert_offset + cfg.held_experts]

    y = _expert_ffn(p, jnp.broadcast_to(xt, (cfg.held_experts,) + xt.shape),
                    cfg, shared=False)                       # (E_l, n, d)
    out = jnp.einsum("end,ne->nd", y, w.astype(x.dtype))
    if cfg.n_shared_experts:
        out = out + _shared(p, xt, cfg)
    zero = jnp.zeros((), jnp.int32)
    return out.reshape(b, s, d), {"balance": aux, "moe_assigned": zero,
                                  "moe_dropped": zero}


def expert_load(p: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Fraction of tokens routed to each expert (for aux-free bias update)."""
    gates, sel, _ = router_scores(p, x.reshape(-1, x.shape[-1]), cfg)
    _, idx = jax.lax.top_k(sel, cfg.top_k)
    mask = jax.nn.one_hot(idx, sel.shape[-1], dtype=bool).any(axis=-2)
    return jnp.mean(mask.astype(jnp.float32), axis=0)


def update_router_bias(bias: jax.Array, load: jax.Array,
                       rate: float = 0.001) -> jax.Array:
    """DeepSeek aux-loss-free balancing: nudge under/over-loaded expert bias.

    load: (E,) fraction of tokens routed to each expert this step.
    """
    target = jnp.mean(load)
    return bias + rate * jnp.sign(target - load)
