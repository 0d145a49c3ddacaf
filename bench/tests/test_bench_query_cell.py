"""The query cell end to end at a small size on the CPU: the harness
finds a cell added as files only, its answers pass the comparison, the
comparison fails the faults a query cell can have, and the bfloat16
control fails it too."""
import filecmp

import numpy as np
import pytest

from bench import run
from bench.gen import lineitem
from bench.ref.query import compare, grouped
from bench.tests import cells

SEED = 2 ** 31 + 101


def run_tiny(tmp_path, name="tiny.q1", seconds=1.0):
    bench, spec = cells.tiny_query_cell(tmp_path, name)
    return bench, run.run_cell(name, SEED, seconds, False, spec=spec,
                               bench_dir=bench, work=tmp_path / "work")


def test_cell_added_as_files_runs_and_is_correct(tmp_path):
    bench, res = run_tiny(tmp_path, "dummy.q1")
    # nothing of the copy was edited or removed: it only gained files
    def edits(cmp):
        return (cmp.diff_files + cmp.left_only
                + [f for sub in cmp.subdirs.values() for f in edits(sub)])
    cmp = filecmp.dircmp(cells.BENCH, bench, ignore=["tests", "__pycache__"])
    assert edits(cmp) == []
    assert (bench / "traffic" / "dummy.q1.json").exists()
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"scan_rows_per_s", "setup_s"}
    assert res["checks"]["rel"]["value"] < res["checks"]["rel"]["limit"]
    assert res["checks"]["count"]["value"] == 0


def test_altered_answer_is_not_correct(tmp_path, monkeypatch):
    from repro.analytics import kernels as K
    real = K.fused_filter_aggregate

    def altered(*a, **kw):
        acc, cnt = real(*a, **kw)
        acc = acc.copy()
        acc[0] = acc[0] * 1.01 + 1          # off by a percent, or one count
        return acc, cnt
    monkeypatch.setattr(K, "fused_filter_aggregate", altered)
    _, res = run_tiny(tmp_path)
    assert not res["correct"]


def test_half_the_partitions_left_out_is_not_correct(tmp_path, monkeypatch):
    from repro.analytics.executor import AnalyticsEngine
    real = AnalyticsEngine._schedule
    monkeypatch.setattr(AnalyticsEngine, "_schedule",
                        lambda self, oids: real(self, oids)[:len(oids) // 2])
    _, res = run_tiny(tmp_path)
    assert not res["correct"]


def test_bfloat16_control_fails_the_limit(tmp_path):
    from bench.drivers import query
    from bench.spans import Spans
    traffic = cells.load(cells.BENCH / "traffic" / "tpch-sf5.q1.json")
    cfg = dict(cells.load(cells.BENCH / "configs"
                          / "tpch-lineitem-sf5.json"), scale_factor=0.01)
    ctx = run.Context("tpch-sf5.q1", traffic, cfg, SEED, 0.0, False,
                      tmp_path, Spans())
    worst = query.control(ctx)
    limits = traffic["limits"]
    assert any(worst[k] > limits[k] for k in limits)
    assert worst["rel"] > 3 * limits["rel"]


def test_reference_is_deterministic():
    cfg = dict(cells.load(cells.BENCH / "configs"
                          / "tpch-lineitem-sf5.json"), scale_factor=0.002)
    cols = lineitem.generate(cfg, SEED)
    q = {"filter": ["<=", "l_shipdate", 10470],
         "group": ["+", ["*", "l_returnflag", 2], "l_linestatus"],
         "aggregates": [{"name": "s", "agg": "sum",
                         "value": ["*", "l_extendedprice", ["-", 1.0,
                                                            "l_discount"]]},
                        {"name": "m", "agg": "mean", "value": "l_tax"},
                        {"name": "c", "agg": "count"}]}
    a, b = grouped(cols, q), grouped(cols, q)
    assert all(np.array_equal(a[k][1], b[k][1]) for k in a)
    assert list(a["c"][0]) == [0, 2, 3, 4]
    keep = cols["l_shipdate"] <= 10470
    assert a["c"][1].sum() == keep.sum()


@pytest.mark.parametrize("agg", ["sum", "count"])
def test_compare_reads_group_and_value_gaps(agg):
    keys = np.array([0, 2, 3])
    want = (keys, np.array([10.0, 20.0, 30.0]))
    assert compare((keys, want[1]), want, agg) == (
        {"groups": 0.0, "count": 0.0} if agg == "count"
        else {"groups": 0.0, "rel": 0.0})
    assert compare((keys[:2], want[1][:2]), want, agg) == {"groups": 1.0}


def test_traced_run_reports_per_layer_metrics(tmp_path, monkeypatch):
    from bench import peaks
    v5e = peaks.load("TPU v5 lite")
    monkeypatch.setattr(peaks, "load", lambda kind: v5e)
    bench, spec = cells.tiny_query_cell(tmp_path)
    res = run.run_cell("tiny.q1", SEED, 1.0, True, spec=spec,
                       bench_dir=bench, work=tmp_path / "work")
    assert res["correct"]
    # the CPU has no device plane: the trace-read metrics say nothing
    assert set(res["metrics"]) == {"front_door_wait_s", "plan_s_per_query",
                                   "exec_s_per_query", "bytes_read_per_row",
                                   "query_p95_s", "scan_mfu"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["metrics"]["scan_mfu"]["unit"] == "%"
    assert res["device"]["busy_s"] == 0.0 and res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
