"""The training cell end to end at a small size on the CPU: correct as
it stands, not correct with the step broken underneath, and the fp8
control of the reference fails its limits."""
import pytest

from bench import run
from bench.tests import cells

SEED = 2 ** 31 + 202


def run_tiny(tmp_path, **kw):
    bench, spec = cells.tiny_train_cell(tmp_path, **kw)
    return run.run_cell("tiny.train", SEED, 0.5, False, spec=spec,
                        bench_dir=bench, work=tmp_path / "work")


def broken_step(monkeypatch, fault):
    import repro.launch.train as T
    real = T.make_train_step

    def make(cfg, run_cfg):
        step = real(cfg, run_cfg)

        def broken(params, opt, batch):
            if fault == "unchanged":
                _, _, metrics = step(params, opt, batch)
                return params, opt, metrics
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(params, opt, half)
        return broken
    monkeypatch.setattr(T, "make_train_step", make)


def test_train_cell_is_correct(tmp_path):
    res = run_tiny(tmp_path)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["attempted"] > 0
    for c in res["checks"].values():
        assert c["value"] < c["limit"] / 10


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(tmp_path, monkeypatch, fault):
    broken_step(monkeypatch, fault)
    assert not run_tiny(tmp_path)["correct"]


def test_fp8_control_fails_the_limits(tmp_path):
    from bench.drivers import train
    from bench.spans import Spans
    traffic = dict(cells.load(cells.BENCH / "traffic"
                              / "train.mamba2-130m.json"), batch=4, seq=64)
    cfg = dict(cells.load(cells.BENCH / "configs" / "mamba2-130m.json"),
               n_layers=2, d_model=64, d_state=16, headdim=16,
               chunk_size=16, vocab_size=256)
    ctx = run.Context("train.mamba2-130m", traffic, cfg, SEED, 0.0, False,
                      tmp_path, Spans())
    gaps = train.control(ctx)
    limits = traffic["limits"]
    assert gaps.pop("skipped") == []
    assert any(gaps[k] > limits[k] for k in limits), gaps


@pytest.mark.parametrize("trace_seconds", [None, 0.3])
def test_traced_run_reports_per_layer_metrics(tmp_path, monkeypatch,
                                              trace_seconds):
    from bench import peaks
    v5e = peaks.load("TPU v5 lite")
    monkeypatch.setattr(peaks, "load", lambda kind: v5e)
    bench, spec = cells.tiny_train_cell(tmp_path,
                                        trace_seconds=trace_seconds)
    res = run.run_cell("tiny.train", SEED, 1.5, True, spec=spec,
                       bench_dir=bench, work=tmp_path / "work")
    assert res["correct"]
    assert set(res["metrics"]) == {"loader_wait_s_per_step", "train_mfu"}
    assert 0 < res["metrics"]["train_mfu"]["value"] < 100
    # the trace stops after trace_seconds, the window runs on
    traced = res["device"]["window_s"]
    assert (traced < 1.0) if trace_seconds else (traced >= 1.5)
