"""chip_smoke.py: its phases at small sizes on the CPU, its refusal to
report a result without a TPU, and the compile-cache rule its entry
points share."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("alone", [False, True])
def test_no_result_without_a_tpu(tmp_path, alone):
    """On the CPU, and copied away from the repo, the script exits
    non-zero and prints no result line."""
    if alone:
        shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
        r = _run(["chip_smoke.py"], tmp_path)
    else:
        r = _run([str(SCRIPT)], tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_query_phase_matches_numpy(smoke, tmp_path):
    # 140000 rows pad to two row blocks per partition
    res = smoke.query_phase(tmp_path, seed=3, partitions=3, rows=140000,
                            kernel_mode="xla-jit")
    assert res["queries_ok"] == ["sum", "count", "min", "max", "mean"]
    assert res["groups_seen"] == smoke.GROUPS
    assert res["fused_compiles"] > 0


def test_query_phase_refuses_another_kernel_mode(smoke, tmp_path):
    with pytest.raises(AssertionError, match="xla-jit"):
        smoke.query_phase(tmp_path, seed=3, partitions=1, rows=1024)


def test_percipience_phase_matches_closed_form(smoke):
    res = smoke.percipience_phase(seed=0, nobj=300, hist=24)
    assert res["max_rel_err"] < smoke.HEAT_RTOL


def test_stream_phase_is_exactly_once(smoke, tmp_path):
    res = smoke.stream_phase(tmp_path, seed=0, producers=2, windows=2,
                             events=24)
    assert res["events"] == 96 and res["windows"] == 4
    assert res["dead_letters"] == 0


def test_train_phase_restores_byte_identical(smoke, tmp_path):
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("mamba2-130m").scaled(dtype="float32")
    res = smoke.train_phase(tmp_path, cfg=cfg, batch=2, seq=32, steps=2)
    assert len(res["losses"]) == 2 and res["restore_identical"]


def test_sharded_phase_on_four_host_devices(smoke, tmp_path):
    """The --chips 4 comparison, on four virtual CPU devices."""
    code = (
        "import sys; from pathlib import Path\n"
        f"sys.path[:0] = [{str(REPO)!r}, {str(REPO / 'src')!r}]\n"
        "import chip_smoke as cs\n"
        "from repro.configs import get_smoke_config\n"
        "cfg = get_smoke_config('mamba2-130m')\n"
        f"r = cs.sharded_phase(Path({str(tmp_path)!r}), cfg=cfg, batch=8,"
        " seq=32)\n"
        "print('DEVICES', r['one_device']['devices'],"
        " r['sharded']['devices'], r['loss_rel_diff'])\n")
    r = _run(["-c", code], REPO, {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stderr[-3000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("DEVICES")][0]
    _, one, four, rel = line.split()
    assert (one, four) == ("1", "4")
    assert float(rel) < smoke.SHARDED_RTOL


def test_compile_cache_dir_rule(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the one fixed
    directory in the checkout."""
    from repro.launch.compile_cache import (CHECKOUT_CACHE_DIR,
                                            enable_compile_cache)
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        assert CHECKOUT_CACHE_DIR == REPO / ".jax_cache"
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
