"""Compile every Pallas kernel for a described TPU v5e chip.

The TPU compiler is installed even where no chip is attached, so these
tests lower each kernel at the sizes the system runs it — a 1 Mi-row
colblock partition for the analytics kernels, 131072 objects for the
heat scan, mamba2-130m / recurrentgemma-9b widths for the model kernels —
and hand it to the chip's compiler, which refuses what interpret mode
lets through (unaligned blocks, primitives Mosaic cannot lower, more
VMEM than a kernel may use).  Nothing runs: a pass says the kernel
compiles, not that it is right or fast.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analytics import kernels as K
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rglru_scan import rglru_scan_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.percipience.heat import heat_scan_pallas

PARTITION_ROWS = (1 << 20) // 128        # 1 Mi values in (rows, 128) lanes
SEG_BLOCKS = 8                           # 1000 groups in 128-segment blocks
HEAT_OBJECTS, HEAT_HIST = 131072, 64


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


_LT50 = json.dumps({"t": "bin", "op": "<", "l": {"t": "col", "i": 1},
                    "r": {"t": "lit", "v": 50}}, sort_keys=True)


@pytest.mark.parametrize("op,dtype,value", [
    ("sum", "int32", {"t": "col", "i": 2}),
    ("count", "int32", None),
    ("min", "int32", {"t": "col", "i": 2}),
    ("max", "int32", {"t": "col", "i": 2}),
    ("sum", "float32", {"t": "col", "i": 2}),
])
def test_fused_filter_aggregate_compiles_at_1m_rows(one_chip, op, dtype,
                                                    value):
    """The pushdown path: 3 columns + ids over a 1 Mi-row partition."""
    call = K._fused_pallas_call(
        PARTITION_ROWS, SEG_BLOCKS, op, dtype, _LT50,
        json.dumps(value, sort_keys=True) if value else "", (0, 1, 2),
        False)
    coldt = jnp.float32 if dtype == "float32" else jnp.int32
    shapes = [((PARTITION_ROWS, 128), jnp.int32)] * 2 + \
        [((PARTITION_ROWS, 128), coldt), ((PARTITION_ROWS, 128), jnp.int32)]
    compiled = _compile(call, shapes, one_chip)
    # every input is read in row blocks: the arguments are the whole
    # partition in HBM, and the kernel itself needs no HBM temporaries
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 4 * (1 << 20) * 4
    assert mem.temp_size_in_bytes == 0


@pytest.mark.parametrize("op,dtype", [("sum", jnp.int32),
                                      ("min", jnp.float32)])
def test_segment_reduce_compiles_at_1m_rows(one_chip, op, dtype):
    call = K._segment_call(PARTITION_ROWS, SEG_BLOCKS, op,
                           jnp.dtype(dtype).name, False)
    _compile(call, [((PARTITION_ROWS, 128), dtype),
                    ((PARTITION_ROWS, 128), jnp.int32)], one_chip)


def test_window_reduce_compiles(one_chip):
    call = K._window_call(64, 16384, "sum", "int32", False)
    _compile(call, [((64, 16384), jnp.int32)], one_chip)


def test_heat_scan_compiles(one_chip):
    fn = functools.partial(heat_scan_pallas, obj_block=128)
    shape = ((HEAT_HIST, HEAT_OBJECTS), jnp.float32)
    _compile(fn, [shape, shape], one_chip)


def test_flash_attention_compiles_at_recurrentgemma_widths(one_chip):
    """Local-attention layer: 16 q heads, 1 kv head (MQA), head_dim 256,
    window 2048."""
    fn = functools.partial(flash_attention_pallas, scale=256 ** -0.5,
                           causal=True, window=2048)
    _compile(fn, [((1, 16, 1024, 256), jnp.bfloat16),
                  ((1, 1, 1024, 256), jnp.bfloat16),
                  ((1, 1, 1024, 256), jnp.bfloat16)], one_chip)


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    """mamba2-130m: d_inner 1536 = 24 heads x 64, state 128, chunk 256."""
    fn = functools.partial(ssd_scan_pallas, chunk=256)
    _compile(fn, [((8, 1024, 24, 64), jnp.float32),
                  ((8, 1024, 24), jnp.float32), ((24,), jnp.float32),
                  ((8, 1024, 1, 128), jnp.float32),
                  ((8, 1024, 1, 128), jnp.float32)], one_chip)


def test_rglru_scan_compiles_at_recurrentgemma_widths(one_chip):
    """recurrentgemma-9b: lru_width 4096."""
    fn = functools.partial(rglru_scan_pallas, chunk=256, width_block=512)
    shape = ((1, 1024, 4096), jnp.float32)
    _compile(fn, [shape, shape], one_chip)
