"""The Nemotron 3 Nano cell at a small size on the CPU: the program's
training forward and gradients against the plain reference, the cell end
to end (correct as it stands, not correct with the expert layer broken),
the fp8 control failing its limits, the FLOP count, and the readers of
the expert layer's metrics."""
import copy
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.tests import cells

SEED = 2 ** 31 + 303
CELL = "train.nemotron3-nano.8k"


def small_config(held=16, offset=0, cf=1.25):
    """Every width cut to a test's size (d 64, 16 experts, top-6, 2
    groups), the published pattern's first period, float32."""
    cfg = cells.load(cells.BENCH / "configs" / "nemotron3-nano-30b-a3b.json")
    return dict(
        cfg, name="nemotron3-tiny", hidden_size=64, mamba_num_heads=8,
        mamba_head_dim=16, n_groups=2, ssm_state_size=16, chunk_size=16,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
        n_routed_experts=held, vocab_size=256, moe_capacity_factor=cf,
        deployment=dict(cfg["deployment"], router_experts=16,
                        expert_offset=offset),
        train=dict(cfg["train"], compute_dtype="float32", microbatch=2))


def seeded(cfg, seed=5):
    """Weights from the generator, with a nonzero correction bias so that
    selection and routing weights differ."""
    from bench.gen import nemotron3 as gen
    w = gen.init_weights(cfg, seed)
    for c, layer in zip(cfg["hybrid_override_pattern"], w["layers"]):
        if c == "E":
            layer["router_bias"] = 0.05 * jax.random.normal(
                jax.random.key(seed), layer["router_bias"].shape)
    return w


# float32 on both sides with the same routing; the gap is the order of
# float32 sums over 7 layers (measured 3e-6 relative at most)
GRAD_RTOL = 1e-4


@pytest.mark.parametrize("held,offset", [(16, 0), (4, 4)])
def test_program_matches_reference(held, offset):
    """Loss, counters and every gradient leaf of the program's training
    forward (capacity 1.25, tokens dropped) against the reference."""
    from bench.drivers.train_nemotron3 import program_config
    from bench.gen import nemotron3 as gen
    from bench.ref import nemotron3 as ref
    from repro.models import model as mdl
    cfg = small_config(held, offset)
    pc = program_config(cfg)
    w = seeded(cfg)
    params = gen.to_program(w, cfg["hybrid_override_pattern"])
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 256, (2, 64)).astype(np.int32)
    labels = rng.integers(0, 256, (2, 64)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
    with jax.default_matmul_precision("highest"):
        (lp, metrics), gp = jax.value_and_grad(
            lambda p: mdl.loss_fn(p, batch, pc), has_aux=True)(params)
        lr, gr = jax.value_and_grad(lambda w: ref.loss(w, toks, labels,
                                                       cfg))(w)
        counts = ref.expert_counts(w, toks, cfg)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    assert (int(metrics["moe_assigned"]), int(metrics["moe_dropped"])) \
        == counts
    assert counts[1] > 0                   # capacity 1.25 dropped tokens
    for name, g in zip(gen.program_names(params), jax.tree.leaves(gp)):
        r = np.asarray(gen.leaf_of(gr, name))
        scale = max(float(np.abs(r).max()), 1e-12)
        assert float(np.abs(np.asarray(g) - r).max()) <= GRAD_RTOL * scale, \
            name


def tiny_cell(tmp_path):
    """The cell at ``small_config``'s size, 2 rows of 64 tokens, as new
    files in a copy of the bench directory."""
    bench = cells.bench_copy(tmp_path)
    traffic = dict(cells.load(cells.BENCH / "traffic" / f"{CELL}.json"),
                   batch=2, seq=64, trace_seconds=None)
    return bench, cells.add_cell(bench, cells.spec(), "tiny.nemotron3",
                                 traffic, small_config())


def run_tiny(tmp_path, trace=False, seconds=0.5):
    bench, spec = tiny_cell(tmp_path)
    return run.run_cell("tiny.nemotron3", SEED, seconds, trace, spec=spec,
                        bench_dir=bench, work=tmp_path / "work")


def test_cell_is_correct(tmp_path):
    res = run_tiny(tmp_path)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for c in res["checks"].values():
        assert c["value"] < c["limit"] / 10


def test_broken_routing_weights_are_not_correct(tmp_path, monkeypatch):
    """Routing weights that leave out the routed scale (as if
    ``routed_scaling_factor`` were 1) fail the comparison."""
    from repro.models import moe
    real = moe.routing_weights
    monkeypatch.setattr(moe, "routing_weights",
                        lambda g, o, cfg: real(g, o, cfg) / 2.5)
    assert not run_tiny(tmp_path)["correct"]


def test_traced_run_reports_the_expert_layer(tmp_path, monkeypatch):
    from bench import peaks
    v5e = peaks.load("TPU v5 lite")
    monkeypatch.setattr(peaks, "load", lambda kind: v5e)
    res = run_tiny(tmp_path, trace=True, seconds=1.0)
    assert res["correct"]
    # no device plane on the CPU: the trace-read metrics fall silent
    assert {"loader_wait_s_per_step", "train_mfu",
            "moe_dropped_share"} <= set(res["metrics"])
    assert 0 < res["metrics"]["moe_dropped_share"]["value"] < 100


def test_fp8_control_fails_the_limits(tmp_path):
    from bench.drivers import train_nemotron3 as drv
    from bench.spans import Spans
    traffic = dict(cells.load(cells.BENCH / "traffic" / f"{CELL}.json"),
                   batch=2, seq=64)
    ctx = run.Context(CELL, traffic, small_config(), SEED, 0.0, False,
                      tmp_path, Spans())
    gaps = drv.control(ctx)
    gaps.pop("skipped")
    limits = traffic["limits"]
    assert any(gaps[k] > limits[k] for k in limits), gaps


def test_hlo_ops_find_the_expert_layer_and_the_step_loop():
    """On a compiled step: instructions in ``sage_moe`` that run as ops,
    and one microbatch loop in the entry computation."""
    from bench.drivers.train_nemotron3 import hlo_ops, program_config
    from bench.drivers.train import run_config
    from repro.launch.steps import make_train_step
    from repro.models import model as mdl
    from repro.optim import init_opt_state
    cfg = small_config()
    pc = program_config(cfg)
    params = jax.eval_shape(lambda: mdl.init_params(
        jax.random.key(0), pc, scan_layers=False))
    opt = jax.eval_shape(lambda: init_opt_state(params))
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
             for k in ("tokens", "labels")}
    text = jax.jit(make_train_step(pc, run_config(cfg))).lower(
        params, opt, batch).compile().as_text()
    scoped, loops = hlo_ops(text, "sage_moe")
    assert len(loops) == 1 and scoped
    assert not set(scoped) & set(loops)


def test_hlo_ops_skip_what_does_not_run_on_its_own():
    from bench.drivers.train_nemotron3 import hlo_ops
    text = "\n".join([
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        '  %a.1 = f32[4]{0} add(%p, %p), metadata={op_name="x/sage_moe/add"}',
        "}",
        "%body.2 (p: (s32[], f32[4])) -> (s32[], f32[4]) {",
        '  %fusion.3 = f32[4]{0} fusion(%g), kind=kLoop, calls=%fused_'
        'computation.1, metadata={op_name="jit(f)/while/body/jvp(sage_moe)'
        '/mul"}',
        '  %copy.4 = f32[4]{0} copy(%g), metadata={op_name="jit(f)/b/c"}',
        "}",
        "%cond.5 (p: (s32[], f32[4])) -> pred[] {",
        "  %lt.6 = pred[] compare(%a, %b), direction=LT",
        "}",
        "ENTRY %main.7 (x: f32[4]) -> f32[4] {",
        "  %while.8 = (s32[], f32[4]{0}) while(%t), condition=%cond.5, "
        'body=%body.2, metadata={op_name="jit(f)/sage_moe/while"}',
        '  ROOT %fusion.9 = f32[4]{0:T(128)} fusion(%x), kind=kLoop, '
        'calls=%fused_computation.1, metadata={op_name="jit(f)/sage_moe/'
        'exp"}',
        "}"])
    assert hlo_ops(text, "sage_moe") == (["fusion.3", "fusion.9"],
                                         ["while.8"])


def _plane(name, events, line="XLA Ops"):
    ev = [SimpleNamespace(name=n, start_ns=s, duration_ns=e - s)
          for n, s, e in events]
    return SimpleNamespace(name=name, lines=[SimpleNamespace(name=line,
                                                             events=ev)])


def test_expert_seconds_per_step_counts_whole_steps():
    """Two whole runs of the step loop and one the trace cut short: the
    scope's ops inside the two whole runs, over two."""
    from bench.tests.cells import BENCH
    reader = run.load_module(BENCH / "metrics" / "moe_expert_s_per_step.py",
                             "moe_expert_s_per_step")
    planes = [
        _plane("/host:CPU", [("bench.window", 0, 10_000)]),
        _plane("/device:TPU:0", [
            ("%while.1 = (s32[]) while(...)", 0, 1000),
            ("%fusion.5 = f32[] fusion(...)", 100, 300),
            ("%fusion.6 = f32[] fusion(...)", 300, 350),
            ("%fusion.7 = f32[] fusion(...)", 400, 900),
            ("%while.1 = (s32[]) while(...)", 1000, 2000),
            ("%fusion.5 = f32[] fusion(...)", 1100, 1400),
            ("%fusion.5 = f32[] fusion(...)", 2100, 2500)])]
    got = reader.seconds_per_step(planes, ["fusion.5", "%fusion.6"],
                                  ["while.1"])
    assert got == pytest.approx((200 + 50 + 300) * 1e-9 / 2)
    assert reader.seconds_per_step(planes, ["fusion.5"], ["while.9"]) is None
    share = run.load_module(BENCH / "metrics" / "moe_dropped_share.py",
                            "moe_dropped_share")
    assert share.reduce({"moe_assigned": 400, "moe_dropped": 30}) == 7.5
    assert share.reduce({}) is None


def test_flops_per_token():
    from bench.flops_nemotron3 import train_flops_per_token
    cfg = cells.load(cells.BENCH / "configs" / "nemotron3-nano-30b-a3b.json")
    got = train_flops_per_token(cfg, 8192)
    # held experts' share: 6 * 8 / 128 of an expert's 4 d f a token
    d, f = 2688, 1856
    routed = 3 * 3 * (6 * 8 / 128) * 4 * d * f
    less = copy.deepcopy(cfg)
    less["n_routed_experts"] = 4
    assert got - train_flops_per_token(less, 8192) == pytest.approx(
        routed / 2)
    # forward per token about 240 M in the Mamba layers, 114 M in the
    # attention layer, 144 M in the expert layers and 88 M in the head
    assert 1.7e9 < got < 1.8e9


def test_config_keeps_the_catalog_widths():
    """Every number of the published config is in the cell's file under
    the same key, changed only where ``reduced`` says."""
    cfg = cells.load(cells.BENCH / "configs" / "nemotron3-nano-30b-a3b.json")
    published = {
        "hidden_size": 2688, "intermediate_size": 1856, "head_dim": 128,
        "mamba_head_dim": 64, "mamba_num_heads": 64, "n_groups": 8,
        "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "num_attention_heads": 32, "num_key_value_heads": 2,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.5}
    assert {k: cfg[k] for k in published} == published
    assert set(cfg["reduced"]) == {"num_hidden_layers",
                                   "hybrid_override_pattern",
                                   "n_routed_experts", "vocab_size"}
    spec = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    entry = [c for c in spec["configs"] if c["name"] == cfg["name"]][0]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert cfg["deployment"]["published_pattern"].startswith(
        cfg["hybrid_override_pattern"])
