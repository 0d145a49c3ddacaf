"""The trace reduction on a trace built here and on one recorded here."""
import pytest

from bench import trace as tr

BUILT = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 7000000 }
  }
  lines {
    id: 2
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 1500000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1 = f32[8]{0} fusion(p)" } }
  event_metadata { key: 2 value { id: 2 name: "copy" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 5000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.next_batch" } }
  event_metadata { key: 3 value { id: 3 name: "bench.step" } }
}
"""


def test_reduce_built_trace():
    from jax.profiler import ProfileData
    s = tr.reduce_planes(ProfileData.from_text_proto(BUILT).planes)
    # window 1000..11000 ns; ops busy [1000, 3500) and [6000, 7000); the
    # op at 21000 ns lies outside the window; module events are not ops
    assert s.devices == 1
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(3.5e-6)
    assert s.ops == pytest.approx({"jit_step/fusion.1": 3.5e-6,
                                   "jit_step/copy": 1e-6})
    # idle [3500, 6000) sits in next_batch (the innermost open span),
    # [7000, 11000) in no span
    assert [g[0] for g in s.gaps] == ["bench.next_batch", "none"]
    assert [g[1] for g in s.gaps] == pytest.approx([2.5e-6, 4e-6])
    assert s.top_gaps(1)[0][0] == "none"
    assert s.top_ops()[0][0] == "jit_step/fusion.1"


def test_interval_helpers():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.complement([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5),
                                                      (6, 10)]
    assert tr.complement([], 0, 4) == [(0, 4)]


def test_reduce_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(tmp_path)
    assert path is not None
    s = tr.reduce_trace(path)
    assert s.window_s > 0
    # the CPU backend has no device plane: nothing is busy, nothing made up
    assert s.devices == 0 and s.busy_s == 0.0
    assert any(line.startswith("plane /host:") for line in tr.describe(path))
