"""Per-arch smoke tests + model-level consistency checks.

Every assigned architecture instantiates a REDUCED config of the same
family and runs one forward/train step on CPU asserting output shapes and
no NaNs (per the assignment); plus decode-vs-full-forward agreement and
the function-preserving property of the TP head-padding transform.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.configs.base import apply_tp_padding
from repro.models import (batch_struct, decode_step, forward_train,
                          init_decode_state, init_params, loss_fn,
                          make_batch, prefill)

KEY = jax.random.key(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train(arch):
    cfg = get_smoke_config(arch)
    params = init_params(KEY, cfg)
    batch = make_batch(KEY, cfg, 2, 16)
    loss, metrics = jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, batch)
    assert jnp.isfinite(loss), f"{arch}: loss not finite"
    logits, aux, hidden = forward_train(params, batch, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert jnp.isfinite(logits).all()
    assert hidden.shape == (2, 16, cfg.d_model)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_serve(arch):
    cfg = get_smoke_config(arch)
    params = init_params(KEY, cfg)
    batch = make_batch(KEY, cfg, 2, 16)
    cache = init_decode_state(cfg, 2, 32)
    logits, cache = jax.jit(lambda p, b, c: prefill(p, b, cfg, c))(
        params, batch, cache)
    assert logits.shape == (2, cfg.vocab_size)
    assert jnp.isfinite(logits).all()
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits2, cache = jax.jit(
        lambda p, t, pos, c: decode_step(p, t, pos, cfg, c))(
        params, tok, jnp.int32(16), cache)
    assert logits2.shape == (2, cfg.vocab_size)
    assert jnp.isfinite(logits2).all()


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "gemma2-27b", "mamba2-130m",
                                  "recurrentgemma-9b", "deepseek-v3-671b"])
def test_decode_matches_full_forward(arch):
    """Prefill(t0..t_{n-1}) + decode(t_n) logits == train forward logits."""
    cfg = get_smoke_config(arch)
    if cfg.is_moe:
        # lossless dispatch for exactness
        cfg = cfg.scaled(moe_capacity_factor=float(cfg.n_experts) / cfg.top_k)
    params = init_params(KEY, cfg, dtype=jnp.float32)
    cfg = cfg.scaled(dtype="float32")
    n = 12
    batch = make_batch(KEY, cfg, 2, n)
    logits_full, _, _ = forward_train(params, batch, cfg)

    cache = init_decode_state(cfg, 2, n + 4, dtype=jnp.float32)
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, : n - 1]
    lg, cache = prefill(params, pre, cfg, cache)
    np.testing.assert_allclose(np.asarray(lg),
                               np.asarray(logits_full[:, n - 2]),
                               atol=2e-3, rtol=2e-3)
    tok = batch["tokens"][:, n - 1: n]
    lg2, cache = decode_step(params, tok, jnp.int32(n - 1), cfg, cache)
    np.testing.assert_allclose(np.asarray(lg2),
                               np.asarray(logits_full[:, n - 1]),
                               atol=2e-3, rtol=2e-3)


def test_tp_padding_is_function_preserving():
    """Padded-config forward == unpadded forward when weights are
    transferred through the head maps."""
    from repro.models.attention import head_maps, _place_heads

    cfg = get_smoke_config("qwen2.5-32b").scaled(
        n_layers=2, n_heads=6, n_kv_heads=2, head_dim=8, dtype="float32")
    cfg_pad = apply_tp_padding(cfg, tp=4)
    assert cfg_pad.n_kv_heads % 4 == 0 and cfg_pad.n_heads % 4 == 0

    params = init_params(KEY, cfg, dtype=jnp.float32)
    params_pad = init_params(KEY, cfg_pad, dtype=jnp.float32)

    qmap, kvmap = head_maps(cfg_pad)

    def transfer(src, dst):
        # axes from the right so stacked (scan) params work too
        dst = dict(dst)
        dst["wq"] = _place_heads(src["wq"], qmap, src["wq"].ndim - 2)
        dst["wo"] = _place_heads(src["wo"], qmap, src["wo"].ndim - 3)
        dst["wk"] = _place_heads(src["wk"], kvmap, src["wk"].ndim - 2)
        dst["wv"] = _place_heads(src["wv"], kvmap, src["wv"].ndim - 2)
        if "bq" in src:
            dst["bq"] = _place_heads(src["bq"], qmap, src["bq"].ndim - 2)
            dst["bk"] = _place_heads(src["bk"], kvmap, src["bk"].ndim - 2)
            dst["bv"] = _place_heads(src["bv"], kvmap, src["bv"].ndim - 2)
        return dst

    # copy non-attention weights verbatim; rewrite attention through maps
    def sync(tree_src, tree_dst):
        if isinstance(tree_src, dict):
            if "wq" in tree_src:
                return transfer(tree_src, tree_dst)
            return {k: sync(tree_src[k], tree_dst[k]) for k in tree_src}
        if isinstance(tree_src, list):
            return [sync(a, b) for a, b in zip(tree_src, tree_dst)]
        return tree_src

    params_pad = sync(params, params_pad)
    batch = make_batch(KEY, cfg, 2, 8)
    out_ref, _, _ = forward_train(params, batch, cfg)
    out_pad, _, _ = forward_train(params_pad, batch, cfg_pad)
    np.testing.assert_allclose(np.asarray(out_pad), np.asarray(out_ref),
                               atol=1e-4, rtol=1e-4)


def test_moe_grouped_matches_dense_oracle():
    from repro.models import moe as moe_lib

    cfg = get_smoke_config("qwen2-moe-a2.7b").scaled(
        moe_capacity_factor=4.0,  # = E/k -> lossless
        dtype="float32")
    p = moe_lib.init_moe(jax.random.key(1), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(2), (2, 16, cfg.d_model), jnp.float32)
    y1, aux1 = moe_lib.moe_block(p, x, cfg)
    y2, aux2 = moe_lib.moe_block_dense(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(float(aux1["balance"]),
                               float(aux2["balance"]), rtol=1e-5)


def test_ssd_chunked_matches_sequential():
    from repro.models.ssm import ssd_chunked, ssd_reference

    b, s, h, p, n = 2, 64, 3, 8, 16
    key = jax.random.key(3)
    x = jax.random.normal(key, (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(4), (b, s, h)))
    a_log = jnp.log(jnp.linspace(1.0, 4.0, h))
    B = jax.random.normal(jax.random.key(5), (b, s, 1, n)) * 0.3
    C = jax.random.normal(jax.random.key(6), (b, s, 1, n)) * 0.3
    y1, f1 = ssd_chunked(x, dt, a_log, B, C, chunk=16)
    y2, f2 = ssd_reference(x, dt, a_log, B, C)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2),
                               atol=2e-4, rtol=1e-3)


def test_local_attention_window():
    """Sliding-window attention must ignore tokens beyond the window."""
    from repro.models import attention as attn

    cfg = get_smoke_config("gemma2-27b").scaled(dtype="float32",
                                                attn_softcap=0.0)
    p = attn.init_attention(jax.random.key(7), cfg, dtype=jnp.float32)
    b, s, d = 1, 24, cfg.d_model
    x = jax.random.normal(jax.random.key(8), (b, s, d))
    pos = jnp.arange(s)[None]
    out_w, _ = attn.self_attention(p, x, pos, cfg, window=cfg.local_window)
    # perturb a token far outside every later query's window
    x2 = x.at[:, 0].add(10.0)
    out_w2, _ = attn.self_attention(p, x2, pos, cfg,
                                    window=cfg.local_window)
    w = cfg.local_window
    np.testing.assert_allclose(np.asarray(out_w[:, w + 1:]),
                               np.asarray(out_w2[:, w + 1:]),
                               atol=1e-5)


def _positions_mask(q_pos, k_pos, causal, window):
    """attend_dense's mask from positions, as attend_chunked reads them."""
    mask = (k_pos[None, :] >= 0) & jnp.ones((q_pos.shape[0], 1), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    return mask


# (sq, sk, chunk, causal, window, query offset, empty key slots)
CHUNKED_CASES = {
    "causal_blocks": (96, 96, 32, True, 0, 0, ()),
    "window_eq_chunk": (96, 96, 32, True, 32, 0, ()),
    "ragged_sq": (80, 80, 32, True, 0, 0, ()),
    "empty_slots": (64, 128, 32, True, 0, 48, tuple(range(32, 64)) + (70, 99)),
}


@pytest.mark.parametrize("case", sorted(CHUNKED_CASES))
def test_chunked_attention_matches_dense(case):
    """attend_chunked, which skips the KV chunks no query of a block can
    see, gives attend_dense's output and gradients of q, k and v."""
    from repro.models import attention as attn
    from repro.configs import get_smoke_config

    sq, sk, chunk, causal, window, off, empty = CHUNKED_CASES[case]
    cfg = get_smoke_config("qwen2.5-32b").scaled(dtype="float32")
    key = jax.random.key(9)
    b, h, kv, hd = 2, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jax.random.normal(key, (b, sq, h, hd))
    k = jax.random.normal(jax.random.key(10), (b, sk, kv, hd))
    v = jax.random.normal(jax.random.key(11), (b, sk, kv, hd))
    ct = jax.random.normal(jax.random.key(12), (b, sq, h, hd))
    q_pos = jnp.arange(sq) + off
    k_pos = jnp.arange(sk).at[jnp.asarray(empty, jnp.int32)].set(-1)
    mask = _positions_mask(q_pos, k_pos, causal, window)

    def dense(q, k, v):
        return attn.attend_dense(q, k, v, mask, cfg)

    def chunked(q, k, v):
        return attn.attend_chunked(q, k, v, q_pos, k_pos, cfg, causal=causal,
                                   window=window, chunk=chunk)[0]

    out_d, vjp_d = jax.vjp(dense, q, k, v)
    out_c, vjp_c = jax.vjp(chunked, q, k, v)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_d),
                               atol=2e-5, rtol=1e-4)
    for g_c, g_d in zip(vjp_c(ct), vjp_d(ct)):
        np.testing.assert_allclose(np.asarray(g_c), np.asarray(g_d),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("window,run", [(0, 36), (8, 15)])
def test_chunked_attention_counts(window, run):
    """Causal attention over 8 blocks runs the 36 chunk pairs on or below
    the diagonal of 64; a window of one chunk runs the diagonal and the
    chunk before it, 15."""
    from repro.models import attention as attn
    from repro.configs import get_smoke_config

    cfg = get_smoke_config("qwen2.5-32b").scaled(dtype="float32")
    s, chunk = 64, 8
    q = jnp.ones((1, s, cfg.n_heads, cfg.head_dim))
    k = jnp.ones((1, s, cfg.n_kv_heads, cfg.head_dim))
    pos = jnp.arange(s)
    _, counts = jax.jit(lambda q, k: attn.attend_chunked(
        q, k, k, pos, pos, cfg, causal=True, window=window, chunk=chunk))(q, k)
    assert (int(counts["attn_chunks_run"]),
            int(counts["attn_chunks_all"])) == (run, 64)


def _step_metrics(arch, seq):
    """Metrics of one train step of two microbatches of one row each."""
    from repro.configs.base import RunConfig
    from repro.launch.steps import make_train_step
    from repro.optim import init_opt_state

    cfg = get_smoke_config(arch)
    params = init_params(KEY, cfg, scan_layers=False)
    batch = make_batch(KEY, cfg, 2, seq)
    run = RunConfig(microbatch=2, remat="none", scan_layers=False)
    step = jax.jit(make_train_step(cfg, run))
    return cfg, step(params, init_opt_state(params), batch)[2]


def test_train_step_sums_attention_counters():
    """The step sums the chunk counters over its microbatches: two of one
    row, each through the one attention layer of MEMEM*E in 2 x 2 chunk
    pairs of which 3 run."""
    from repro.models import attention as attn
    from repro.configs.base import ATTN_ONLY

    attn.set_attention_impl("chunked")
    try:
        cfg, m = _step_metrics("nemotron3-nano-30b-a3b", 2048)
    finally:
        attn.set_attention_impl("auto")
    assert cfg.attn_pattern.count(ATTN_ONLY) == 1
    assert (int(m["attn_chunks_run"]), int(m["attn_chunks_all"])) == (6, 8)


def test_attention_counters_zero_without_attention():
    _, m = _step_metrics("mamba2-130m", 32)
    assert (int(m["attn_chunks_run"]), int(m["attn_chunks_all"])) == (0, 0)


@pytest.mark.parametrize("remat", ["dots", "full"])
def test_unrolled_layers_honour_remat(remat):
    """With the layers unrolled, remat recomputes the blocks in the
    backward pass and changes neither the loss nor the gradient."""
    cfg = get_smoke_config("nemotron3-nano-30b-a3b").scaled(dtype="float32")
    params = init_params(KEY, cfg, scan_layers=False)
    batch = make_batch(KEY, cfg, 2, 32)

    def grads(r):
        fn = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, remat=r)[0])
        return jax.jit(fn)(params), jax.jit(fn).lower(params).as_text()

    (l0, g0), text0 = grads("none")
    (l1, g1), text1 = grads(remat)
    assert text1 != text0
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
