"""The program's spans read from a built trace: ``sage.*`` spans on two
threads clipped to the window and summed, the idle gaps labelled by the
innermost ``sage.*`` span, and each new metric dividing by answered
requests or by steps, or saying nothing where there is nothing to read."""
from types import SimpleNamespace

import pytest

from bench import program_spans, run
from bench.tests import cells

NEW_METRICS = ("store_read_s_per_query", "key_build_s_per_query",
               "h2d_s_per_query", "kernel_wait_s_per_query",
               "loader_queue_wait_s_per_step")

DEVICE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 2
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "sage_fused_filter_agg.1" } }
}
"""

# window [1000, 11000) ns; device busy [1000, 3000) and [6000, 7000), so
# idle [3000, 6000) (middle 4500) and [7000, 11000) (middle 9000).  Times
# below are ns; offsets in the proto are ps from 0.
HOST = """
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "sage-analytics_0"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 500000 duration_ps: 7500000 }
    events { metadata_id: 4 offset_ps: 4000000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "sage-serve-0"
    timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 9500000 duration_ps: 2500000 }
    events { metadata_id: 5 offset_ps: 8500000 duration_ps: 3500000 }
    events { metadata_id: 6 offset_ps: 2000000 duration_ps: 200000 }
    events { metadata_id: 6 offset_ps: 4000000 duration_ps: 400000 }
    events { metadata_id: 6 offset_ps: 12000000 duration_ps: 500000 }
    events { metadata_id: 7 offset_ps: 3000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "sage.store.read" } }
  event_metadata { key: 3 value { id: 3 name: "sage.exec.partition" } }
  event_metadata { key: 4 value { id: 4 name: "sage.kernel.wait" } }
  event_metadata { key: 5 value { id: 5 name: "sage.serve.request" } }
  event_metadata { key: 6 value { id: 6 name: "sage.loader.wait" } }
  event_metadata { key: 7 value { id: 7 name: "bench.wait" } }
}
"""


def planes(text):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text).planes


def test_spans_on_two_threads_are_clipped_and_summed():
    ps = program_spans.reduce_planes(planes(DEVICE + HOST))
    assert ps.window_s == pytest.approx(10e-6)
    # [500, 2500) and [9500, 12000) cross the window's edges: 1500 ns each
    assert ps.counts["sage.store.read"] == 2
    assert ps.seconds["sage.store.read"] == pytest.approx(3e-6)
    assert ps.seconds["sage.exec.partition"] == pytest.approx(7e-6)
    # the loader span after the window is not counted
    assert ps.counts["sage.loader.wait"] == 2
    assert ps.seconds["sage.loader.wait"] == pytest.approx(6e-7)
    assert not any(n.startswith("bench.") for n in ps.seconds)


def test_gaps_are_labelled_by_the_innermost_sage_span():
    ps = program_spans.reduce_planes(planes(DEVICE + HOST))
    # at 4500 the kernel wait is the shortest sage span open (inside the
    # partition; the loader wait closed at 4400); at 9000 the request is
    # the one sage span open; bench spans never label
    assert [g[0] for g in ps.gaps] == ["sage.kernel.wait",
                                       "sage.serve.request"]
    assert [g[1] for g in ps.gaps] == pytest.approx([3e-6, 4e-6])
    assert ps.gap_table() == {"sage.serve.request": (1, pytest.approx(4e-6)),
                              "sage.kernel.wait": (1, pytest.approx(3e-6))}
    assert ps.top_gaps(1)[0][0] == "sage.serve.request"


def test_window_without_its_span_is_the_extent_of_ops_and_bench_spans():
    host = HOST.replace('name: "bench.window"', 'name: "other"')
    ps = program_spans.reduce_planes(planes(DEVICE + host))
    # device ops [1000, 7000), bench.wait [3000, 6000): the window is
    # [1000, 7000) and the span [9500, 12000) lies outside it
    assert ps.window_s == pytest.approx(6e-6)
    assert ps.counts["sage.store.read"] == 1
    assert ps.seconds["sage.store.read"] == pytest.approx(1.5e-6)


def _view(requests=3, devices=1):
    return {"trace": SimpleNamespace(devices=devices),
            "requests": [{}] * requests}


def _reducers():
    return {m: run.load_module(cells.BENCH / "metrics" / f"{m}.py",
                               "bench_metric_" + m) for m in NEW_METRICS}


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    """A built trace written where ``load`` looks for one."""
    from jax.profiler import ProfileData

    def write(text):
        d = tmp_path / "trace" / "plugins" / "profile" / "run"
        d.mkdir(parents=True, exist_ok=True)
        (d / "host.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(text))
        program_spans._reduce_file.cache_clear()
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path / "trace")
    yield write
    program_spans._reduce_file.cache_clear()


def test_metrics_divide_by_answered_requests_and_steps(traced):
    traced(DEVICE + HOST)
    got = {m: r.reduce(_view(requests=3)) for m, r in _reducers().items()}
    assert got["store_read_s_per_query"] == pytest.approx(3e-6 / 3)
    assert got["kernel_wait_s_per_query"] == pytest.approx(1e-6 / 3)
    # no such span in this trace: nothing to report
    assert got["key_build_s_per_query"] is None
    assert got["h2d_s_per_query"] is None
    # the loader: the spans' seconds over their count in the window
    assert got["loader_queue_wait_s_per_step"] == pytest.approx(3e-7)
    assert _reducers()["store_read_s_per_query"].reduce(
        _view(requests=0)) is None


def test_every_new_metric_is_none_without_a_device_plane(traced):
    reducers = _reducers()
    # no trace at all, and a summary with no device
    assert all(r.reduce(_view()) is None for r in reducers.values())
    traced(HOST)
    assert program_spans.reduce_planes(planes(HOST)) is None
    assert all(r.reduce(_view()) is None for r in reducers.values())
    assert all(r.reduce(_view(devices=0)) is None
               for r in reducers.values())


def test_a_program_without_spans_reports_nothing(traced):
    """The parent program has no ``sage.*`` spans: each new metric says
    nothing and none raises."""
    bench_only = HOST.replace('name: "sage.', 'name: "other.')
    traced(DEVICE + bench_only)
    assert all(r.reduce(_view()) is None for r in _reducers().values())


def test_cli_prints_the_table(traced, tmp_path, capsys):
    traced(DEVICE + HOST)
    assert program_spans.main([str(tmp_path / "trace")]) == 0
    out = capsys.readouterr().out
    assert "sage.store.read, 2, " in out
    assert "sage.kernel.wait, 1, " in out
