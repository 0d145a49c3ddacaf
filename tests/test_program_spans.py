"""The program's own spans: ``addb.span`` annotates a stage in the
profiler's trace and adds its seconds to the ``QueryStats`` field the
thread works for; a grouped query through ``QueryService`` reports its
per-stage seconds; the heat kernel's builder is cached."""
import glob
import threading
import time

import numpy as np
import pytest

from repro.analytics.executor import QueryStats
from repro.core.addb import span, working_for


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData
    [path] = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    return {ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def test_span_emits_its_name_and_fills_the_field(tmp_path):
    import jax
    stats = QueryStats()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with working_for(stats, threading.Lock()):
            with span("sage.test.stage", "read_s"):
                time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    assert "sage.test.stage" in _host_event_names(tmp_path)
    assert 0.02 <= stats.read_s < 1.0
    assert stats.keys_s == 0.0


def test_span_without_current_stats_only_annotates():
    with span("sage.test.stage", "read_s"):
        pass
    seen = []

    def other():
        # another thread works for nothing, whatever this one does
        with span("sage.test.stage", "read_s"):
            seen.append(True)
    stats = QueryStats()
    with working_for(stats):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen == [True] and stats.read_s == 0.0


@pytest.mark.parametrize("nested", ["same", "other"])
def test_reentered_span_is_timed_once(nested):
    stats = QueryStats()
    with working_for(stats):
        with span("sage.test.outer", "read_s"):
            inner = ("sage.test.outer" if nested == "same"
                     else "sage.test.inner")
            with span(inner, "read_s"):
                time.sleep(0.02)
    # the same name nested adds once; two names add both
    assert (stats.read_s < 0.04) == (nested == "same")


def test_working_for_restores_the_previous_target():
    outer, inner = QueryStats(), QueryStats()
    with working_for(outer):
        with working_for(inner):
            with span("sage.test.stage", "keys_s"):
                time.sleep(0.005)
        with span("sage.test.stage", "h2d_s"):
            time.sleep(0.005)
    with span("sage.test.stage", "h2d_s"):
        pass
    assert inner.keys_s > 0 and inner.h2d_s == 0
    assert outer.h2d_s > 0 and outer.keys_s == 0


def test_grouped_query_reports_stage_seconds(sage):
    from repro.serving import QueryRequest, TenantConfig
    rng = np.random.default_rng(7)
    rows = 4096
    for i in range(4):
        sage.put_columnar(f"lineitem/p{i}", [
            rng.integers(0, 4, rows).astype(np.int32),
            rng.integers(0, 100, rows).astype(np.int32),
            rng.random(rows).astype(np.float32)], container="lineitem")
    ops = ({"op": "filter", "expr": {"t": "bin", "op": "<",
                                     "l": {"t": "col", "i": 1},
                                     "r": {"t": "lit", "v": 50}}},
           {"op": "key_by", "key": {"t": "col", "i": 0}},
           {"op": "aggregate", "agg": "sum",
            "value": {"t": "col", "i": 2}})
    svc = sage.serving([TenantConfig("t")], workers=2, max_workers=4,
                       partial_cache_size=0)
    try:
        r = svc.query(QueryRequest("t", "lineitem", ops), timeout=120)
    finally:
        svc.close()
    assert r.ok and r.stats.partitions == 4
    stages = ("read_s", "keys_s", "h2d_s", "kernel_s")
    for k in stages:
        assert getattr(r.stats, k) > 0, k
        assert r.trace[k] == getattr(r.stats, k)
    assert r.stats.plan_s > 0 and r.stats.exec_s > 0
    assert r.stats.merge_s > 0
    # partition-seconds: each stage is a part of the partitions' work,
    # which runs four at a time at most
    assert sum(getattr(r.stats, k) for k in stages) < 4 * r.stats.exec_s


def test_heat_kernel_builder_is_cached():
    from repro.analytics import kernels as K
    from repro.percipience.heat import _heat_call, heat_scores
    ts = np.sort(np.random.default_rng(0).random((5, 16)) * 100, axis=1)
    mask = np.ones_like(ts)
    heat_scores(ts, mask, now=100.0)
    before, calls = K.kernel_cache_info(), _heat_call.cache_info()
    again = heat_scores(ts + 1.0, mask, now=101.0)
    assert _heat_call.cache_info().hits == calls.hits + 1
    assert _heat_call.cache_info().misses == calls.misses
    after = K.kernel_cache_info()
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]
    assert np.all(np.isfinite(again))


def test_spans_on_many_threads_lose_no_update(monkeypatch):
    """Partition threads add to one QueryStats under its lock: with a
    per-thread clock that advances 0.5 s at each reading, every span
    lasts 0.5 s, and the field is half the number of spans."""
    import sys
    from repro.core import addb
    ticks = threading.local()

    def clock():
        ticks.n = getattr(ticks, "n", 0.0) + 0.5
        return ticks.n
    monkeypatch.setattr(addb.time, "perf_counter", clock)
    stats, lock = QueryStats(), threading.Lock()
    threads_n, spans_n = 16, 200

    def work():
        with working_for(stats, lock):
            for _ in range(spans_n):
                with span("sage.test.stage", "kernel_s"):
                    pass
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(prev)
    assert not any(t.is_alive() for t in threads)
    assert stats.kernel_s == 0.5 * threads_n * spans_n
