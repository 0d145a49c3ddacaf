"""The expert share (``models/moe.py``): shares of the experts add up to
the uncut layer, the dispatch equals the dense oracle where it is
lossless, its counters count, and its work has no scatter; plus the
grouped gated norm and the hybrid pattern that Nemotron-H brings."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ATTN_ONLY, MOE, SSD, hybrid_pattern
from repro.models import common
from repro.models import moe as moe_lib

# float32 throughout; sums of 4 shares against one layer differ by the
# order of float32 additions (about 1e-7 of the output's scale)
TOL = dict(atol=1e-5, rtol=1e-5)


def nemotron(**kw):
    return get_smoke_config("nemotron3-nano-30b-a3b").scaled(
        dtype="float32", **kw)


def share_params(p, cfg, offset, held):
    """The uncut layer's parameters as the share [offset, offset+held)
    holds them: the router and shared expert whole, its experts' slice."""
    out = dict(p)
    for k in ("w_up", "w_down", "w_gate"):
        if k in p:
            out[k] = p[k][offset:offset + held]
    return out


def inputs(cfg, b=2, s=32, seed=2):
    return jax.random.normal(jax.random.key(seed), (b, s, cfg.d_model),
                             jnp.float32)


def with_bias(p, cfg):
    """A nonzero correction bias, so that selection and weights differ."""
    return dict(p, router_bias=0.05 * jax.random.normal(
        jax.random.key(7), (cfg.n_experts,)))


@pytest.mark.parametrize("cf", [1.25, 16 / 6])
def test_shares_sum_to_the_uncut_layer(cf):
    """4 shares of 4 of the 16 experts, the shared expert counted once,
    add up to the layer that holds all 16, with capacity (cf 1.25, where
    tokens drop) and without (cf = E/k)."""
    cfg = nemotron(moe_capacity_factor=cf)
    p = with_bias(moe_lib.init_moe(jax.random.key(1), cfg), cfg)
    x = inputs(cfg)
    whole, aux = moe_lib.moe_block(p, x, cfg)
    shared = moe_lib._shared(p, x, cfg)
    total, assigned, dropped = -3 * shared, 0, 0
    for off in range(0, 16, 4):
        c = cfg.scaled(n_local_experts=4, expert_offset=off)
        y, a = moe_lib.moe_block(share_params(p, cfg, off, 4), x, c)
        total = total + y
        assigned += int(a["moe_assigned"])
        dropped += int(a["moe_dropped"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), **TOL)
    assert assigned == int(aux["moe_assigned"]) == 2 * 32 * cfg.top_k
    assert dropped == int(aux["moe_dropped"])
    assert (dropped > 0) == (cf < 2)


@pytest.mark.parametrize("held,offset", [(16, 0), (4, 8)])
def test_lossless_share_matches_dense_oracle(held, offset):
    cfg = nemotron(moe_capacity_factor=16 / 6, n_local_experts=held,
                   expert_offset=offset)
    p = with_bias(moe_lib.init_moe(jax.random.key(3), cfg), cfg)
    x = inputs(cfg, seed=4)
    y1, a1 = moe_lib.moe_block(p, x, cfg)
    y2, a2 = moe_lib.moe_block_dense(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), **TOL)
    np.testing.assert_allclose(float(a1["balance"]), float(a2["balance"]),
                               rtol=1e-6)
    assert int(a1["moe_dropped"]) == 0


def test_capacity_keeps_the_highest_priorities():
    """One expert, capacity 3 of 8 tokens: it keeps the 3 tokens it
    scores highest and drops the rest; empty slots read zero."""
    cfg = nemotron(n_experts=2, top_k=1, moe_capacity_factor=0.75,
                   n_local_experts=1)
    assert moe_lib.capacity(cfg, 8) == 3
    sel = jnp.array([[.9, .1], [.2, .8], [.7, .3], [.95, .05], [.6, .4],
                     [.1, .9], [.8, .2], [.3, .7]])
    top_e, slot_asg, asg_slot, assigned, dropped = moe_lib.routing_maps(
        sel, 1, 1, 0, 3)
    # tokens routed to expert 0: 0, 2, 3, 4, 6; best three: 3, 0, 6
    assert list(np.asarray(slot_asg)) == [3, 0, 6]
    assert list(np.asarray(asg_slot)) == [1, 3, 3, 0, 3, 3, 2, 3]
    assert (int(assigned), int(dropped)) == (5, 2)
    # holding expert 1 instead: tokens 1, 5, 7, none dropped
    _, slot_asg, asg_slot, assigned, dropped = moe_lib.routing_maps(
        sel, 1, 1, 1, 3)
    assert list(np.asarray(slot_asg)) == [5, 1, 7]
    assert list(np.asarray(asg_slot)) == [3, 1, 3, 3, 3, 0, 3, 2]
    assert (int(assigned), int(dropped)) == (3, 0)


@pytest.mark.parametrize("arch", ["nemotron3-nano-30b-a3b", "qwen2-moe-a2.7b"])
def test_expert_layer_has_no_scatter(arch):
    """Forward and backward of the expert layer lower without a scatter:
    dispatch, combine and the weights move by gathers both ways."""
    cfg = get_smoke_config(arch).scaled(dtype="float32",
                                        moe_capacity_factor=1.25)
    p = moe_lib.init_moe(jax.random.key(1), cfg)
    x = inputs(cfg)

    def loss(p, x):
        y, aux = moe_lib.moe_block(p, x, cfg)
        return jnp.sum(y * y) + aux["balance"]

    for fn in (loss, jax.grad(loss, argnums=(0, 1))):
        text = jax.jit(fn).lower(p, x).as_text()
        assert "scatter" not in text
        assert "gather" in text


def test_expert_layer_gradient_matches_dense_oracle():
    cfg = nemotron(moe_capacity_factor=16 / 6)
    p = with_bias(moe_lib.init_moe(jax.random.key(5), cfg), cfg)
    x = inputs(cfg, seed=6)

    def loss(fn):
        return lambda p, x: jnp.sum(jnp.sin(fn(p, x, cfg)[0]))

    g1 = jax.grad(loss(moe_lib.moe_block), argnums=(0, 1))(p, x)
    g2 = jax.grad(loss(moe_lib.moe_block_dense), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)


def test_group_norm_of_one_group_is_the_whole_width_norm():
    """mamba2-130m's gated norm (one group) is bit-identical to before."""
    x = jax.random.normal(jax.random.key(0), (2, 16, 96), jnp.float32)
    scale = jax.random.normal(jax.random.key(1), (96,))
    for dt in (jnp.float32, jnp.bfloat16):
        a = jax.jit(lambda x, s: common.rms_norm(x, s, 1e-5))(
            x.astype(dt), scale)
        b = jax.jit(lambda x, s: common.group_rms_norm(x, s, 1e-5, 1))(
            x.astype(dt), scale)
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_group_norm_normalises_each_group():
    x = jax.random.normal(jax.random.key(2), (3, 64), jnp.float32)
    x = x * jnp.repeat(jnp.array([1.0, 10.0, 0.1, 3.0]), 16)
    y = common.group_rms_norm(x, jnp.ones(64), 0.0, 4)
    rms = np.sqrt(np.mean(np.square(np.asarray(y).reshape(3, 4, 16)), -1))
    np.testing.assert_allclose(rms, 1.0, rtol=1e-5)


def test_hybrid_pattern():
    assert hybrid_pattern("MEM*") == (SSD, MOE, SSD, ATTN_ONLY)
    with pytest.raises(ValueError):
        hybrid_pattern("MX")
    cfg = get_smoke_config("nemotron3-nano-30b-a3b")
    assert cfg.attn_pattern == hybrid_pattern("MEMEM*E")
