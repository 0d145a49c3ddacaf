"""Distribution tests: sharding rules produce valid specs for every arch,
and a miniature dry-run (8 host devices, 2x4 mesh) lowers + compiles a
sharded train step and a decode step in a subprocess."""
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import apply_tp_padding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_are_divisible(arch):
    """Every sharded dim must divide the production mesh axis size."""
    from repro.distributed.sharding import make_param_specs
    from repro.models import model as mdl

    class FakeMesh:
        axis_names = ("data", "model")

        class devices:
            shape = (16, 16)

    cfg = apply_tp_padding(get_config(arch), 16)
    params = jax.eval_shape(
        lambda: mdl.init_params(jax.random.key(0), cfg))
    specs = make_param_specs(params, cfg, FakeMesh(), fsdp=True)
    sizes = {"data": 16, "model": 16}

    def check(path, leaf, spec):
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            total = 1
            for a in axes:
                total *= sizes[a]
            assert leaf.shape[dim] % total == 0, (
                f"{arch}: {path} dim {dim} size {leaf.shape[dim]} "
                f"not divisible by {ax}={total}")

    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), params, specs)


def _run_subprocess(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=500,
                       cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_mini_dryrun_train_and_decode():
    """2x4 mesh over 8 host CPU devices: a reduced qwen config train step
    and decode step lower + compile with full sharding machinery."""
    out = _run_subprocess(r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec
from repro.configs import get_smoke_config
from repro.configs.base import RunConfig, apply_tp_padding
from repro.distributed.sharding import (default_axis_rules, make_batch_specs,
                                        make_cache_specs, make_param_specs)
from repro.launch.steps import make_decode_step, make_train_step
from repro.models import model as mdl
from repro.models.common import axis_rules
from repro.launch.mesh import make_host_mesh, mesh_context
from repro.optim import AdamWState

mesh = make_host_mesh(2, 4)
cfg = apply_tp_padding(
    get_smoke_config("qwen2.5-32b").scaled(
        n_heads=8, n_kv_heads=2, d_ff=128, vocab_size=256), 4)
rules = default_axis_rules(mesh)

params = jax.eval_shape(lambda: mdl.init_params(jax.random.key(0), cfg,
                                                dtype=jnp.bfloat16))
pspecs = make_param_specs(params, cfg, mesh, fsdp=True)
withsh = lambda t, s: jax.tree.map(
    lambda a, b: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                      sharding=NamedSharding(mesh, b)), t, s)
params = withsh(params, pspecs)
opt = AdamWState(
    step=jax.ShapeDtypeStruct((), jnp.int32,
                              sharding=NamedSharding(mesh, PartitionSpec())),
    m=jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                                  sharding=s.sharding), params),
    v=jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                                  sharding=s.sharding), params))
batch = mdl.batch_struct(cfg, 8, 32)
batch = withsh(batch, make_batch_specs(batch, mesh))

run = RunConfig(remat="full")
with mesh_context(mesh), axis_rules(rules):
    c1 = jax.jit(make_train_step(cfg, run)).lower(params, opt, batch).compile()
    ca = c1.cost_analysis()
    print("TRAIN_COMPILED", int(ca.get("flops", 0)) > 0)

    cache = jax.eval_shape(lambda: mdl.init_decode_state(cfg, 8, 64))
    cache = withsh(cache, make_cache_specs(cache, cfg, mesh))
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32,
                               sharding=NamedSharding(mesh, PartitionSpec("data")))
    pos = jax.ShapeDtypeStruct((), jnp.int32,
                               sharding=NamedSharding(mesh, PartitionSpec()))
    c2 = jax.jit(make_decode_step(cfg)).lower(params, cache, tok, pos).compile()
    print("DECODE_COMPILED", c2.memory_analysis() is not None)
""")
    assert "TRAIN_COMPILED True" in out
    assert "DECODE_COMPILED True" in out


def test_sharded_train_numerics_match_single_device():
    """Loss on a 2x2 mesh == loss on 1 device (same params/batch)."""
    out = _run_subprocess(r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.configs import get_smoke_config
from repro.configs.base import apply_tp_padding
from repro.distributed.sharding import (default_axis_rules, make_batch_specs,
                                        make_param_specs)
from repro.models import model as mdl
from repro.models.common import axis_rules
from repro.launch.mesh import make_host_mesh, mesh_context

cfg = apply_tp_padding(get_smoke_config("internlm2-20b").scaled(
    dtype="float32", n_heads=4, n_kv_heads=2), 2)
params = mdl.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
batch = mdl.make_batch(jax.random.key(1), cfg, 4, 16)
loss_single, _ = jax.jit(lambda p, b: mdl.loss_fn(p, b, cfg))(params, batch)

mesh = make_host_mesh(2, 2)
rules = default_axis_rules(mesh)
pspecs = make_param_specs(params, cfg, mesh, fsdp=True)
params_sh = jax.device_put(params, jax.tree.map(
    lambda s: NamedSharding(mesh, s), pspecs))
batch_sh = jax.device_put(batch, jax.tree.map(
    lambda s: NamedSharding(mesh, s), make_batch_specs(batch, mesh)))
with mesh_context(mesh), axis_rules(rules):
    loss_sh, _ = jax.jit(lambda p, b: mdl.loss_fn(p, b, cfg))(params_sh, batch_sh)
np.testing.assert_allclose(float(loss_single), float(loss_sh), rtol=2e-5)
print("NUMERICS_MATCH", float(loss_single), float(loss_sh))
""")
    assert "NUMERICS_MATCH" in out
