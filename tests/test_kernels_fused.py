"""Fused filter→aggregate kernel + columnar layout validation.

Covers the ISSUE's kernel test checklist: tiling edge cases (row counts
off the 8x128 grid), blocks left empty by the filter, mixed int/float
columns, randomized op chains asserting fused-vs-unfused identity
(seeded via ``SAGE_CHAOS_SEEDS`` like the chaos gauntlets), the colblock
wire format with pruned ranged reads, and the executor's pruned-ship /
double-buffer integration.
"""
import os

import numpy as np
import pytest

from repro.analytics import kernels as K
from repro.analytics.exprs import col, lit
from repro.analytics.plan import (Aggregate, Filter, KeyBy, KernelCfg,
                                  Select, apply_ops, frag_columns,
                                  fuse_chain, op_to_spec, prunable_columns)
from repro.core.columnar import (ColumnBatch, column_nbytes, encode_columns)

SEEDS = [int(s) for s in
         os.environ.get("SAGE_CHAOS_SEEDS", "7").split(",") if s.strip()]

PRED = {"t": "bin", "op": ">=",
        "l": {"t": "col", "i": 0}, "r": {"t": "lit", "v": 50}}
VAL = {"t": "col", "i": 0}


def _fused_both(cols, pred, val, ids, n, **kw):
    """Run interpret-Pallas and the compiled dispatch, assert they
    agree, return one of them."""
    a1, c1 = K.fused_filter_aggregate(cols, pred, val, ids, n,
                                      interpret=True, **kw)
    a2, c2 = K.fused_filter_aggregate(cols, pred, val, ids, n,
                                      interpret=False, **kw)
    np.testing.assert_array_equal(c1, c2)
    if np.issubdtype(a1.dtype, np.integer):
        np.testing.assert_array_equal(a1, a2)
    else:
        np.testing.assert_allclose(a1, a2, rtol=1e-5, atol=1e-5)
    return a1, c1


@pytest.mark.parametrize("rows", [1, 7, 8, 127, 128, 129, 1000, 1025])
@pytest.mark.parametrize("op", ["sum", "count", "min", "max"])
def test_tiling_edges(rows, op):
    rng = np.random.default_rng(rows)
    c0 = rng.integers(0, 100, rows).astype(np.int32)
    ids = rng.integers(0, 5, rows).astype(np.int32)
    val = None if op == "count" else VAL
    acc, cnt = _fused_both({0: c0}, PRED, val, ids, 5, op=op)
    ra, rc = K.fused_filter_aggregate_ref({0: c0}, PRED, val, ids, 5, op=op)
    np.testing.assert_array_equal(acc, ra)
    np.testing.assert_array_equal(cnt, rc)


def test_empty_after_filter():
    c0 = np.zeros(640, np.int32)              # predicate >= 50: none pass
    ids = np.arange(640, dtype=np.int32) % 4
    acc, cnt = _fused_both({0: c0}, PRED, VAL, ids, 4, op="sum")
    assert (cnt == 0).all() and (acc == 0).all()
    acc, cnt = _fused_both({0: c0}, PRED, VAL, ids, 4, op="min")
    assert (acc == np.iinfo(np.int32).max).all()


def test_zero_rows_and_zero_segments():
    acc, cnt = K.fused_filter_aggregate({0: np.zeros(0, np.int32)}, PRED,
                                        VAL, np.zeros(0, np.int32), 3,
                                        op="sum", interpret=True)
    assert acc.shape == (3,) and (cnt == 0).all()
    acc, cnt = K.fused_filter_aggregate({0: np.zeros(4, np.int32)}, PRED,
                                        VAL, np.zeros(4, np.int32), 0,
                                        op="sum", interpret=True)
    assert acc.shape == (0,)


def test_mixed_int_float_columns():
    rng = np.random.default_rng(3)
    rows = 513
    cols = {0: rng.integers(0, 100, rows).astype(np.int32),
            1: rng.standard_normal(rows).astype(np.float32)}
    ids = rng.integers(0, 3, rows).astype(np.int32)
    val = {"t": "col", "i": 1}
    acc, cnt = _fused_both(cols, PRED, val, ids, 3, op="sum")
    ra, rc = K.fused_filter_aggregate_ref(cols, PRED, val, ids, 3, op="sum")
    np.testing.assert_array_equal(cnt, rc)
    np.testing.assert_allclose(acc, ra, rtol=1e-5)
    assert acc.dtype == np.float32


def test_negative_ids_drop_rows():
    c0 = np.full(100, 99, np.int32)
    ids = np.full(100, -1, np.int32)
    ids[:10] = 0
    acc, cnt = _fused_both({0: c0}, None, VAL, ids, 1, op="sum")
    assert cnt[0] == 10 and acc[0] == 990


def _random_chain(rng):
    """A random fusible-or-not op chain over 4 int32 columns."""
    ops = []
    if rng.random() < 0.8:
        thr = int(rng.integers(0, 100))
        ops.append(Filter(col(1) >= lit(thr)))
    if rng.random() < 0.3:
        ops.append(Filter((col(2) % lit(7)) != lit(0)))
    if rng.random() < 0.3:
        ops.append(Select((0, 1, 2)))
    if rng.random() < 0.5:
        ops.append(KeyBy(col(0) if rng.random() < 0.7
                         else (col(0) + col(2) % lit(3))))
        agg = rng.choice(["sum", "count", "mean", "min", "max"])
        ops.append(Aggregate(agg, None if agg == "count" else col(2)))
    else:
        agg = rng.choice(["sum", "count", "min", "max"])
        ops.append(Aggregate(agg, None if agg == "count" else col(2)))
    return ops


def _assert_partials_equal(p1, p2):
    assert p1[0] == p2[0] and p1[1] == p2[1]
    if p1[0] == "scalar":
        v1, v2 = p1[2], p2[2]
        if v1 is None or v2 is None:
            assert v1 is None and v2 is None
        elif isinstance(v1, float) or isinstance(v2, float):
            np.testing.assert_allclose(v1, v2)
        else:
            assert v1 == v2
    else:
        np.testing.assert_array_equal(p1[2], p2[2])
        a, b = p1[3], p2[3]
        if isinstance(a, tuple):
            np.testing.assert_allclose(a[0], b[0], rtol=1e-5)
            np.testing.assert_array_equal(a[1], b[1])
        elif np.issubdtype(np.asarray(a).dtype, np.integer):
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5)


@pytest.mark.parametrize("seed", SEEDS)
def test_randomized_chains_fused_vs_unfused(seed):
    """Chaos sweep: random chains over random blocks — the fused path
    must be indistinguishable from the unfused interpreter (exact on
    integer aggregates)."""
    rng = np.random.default_rng(seed)
    fused = KernelCfg(use_kernel=True, interpret=True, fuse=True)
    unfused = KernelCfg(use_kernel=True, interpret=True, fuse=False)
    for trial in range(20):
        rows = int(rng.integers(0, 600))
        a = np.empty((rows, 4), np.int32)
        a[:, 0] = rng.integers(0, 8, rows)
        a[:, 1] = rng.integers(0, 100, rows)
        a[:, 2] = rng.integers(-40, 40, rows)
        a[:, 3] = trial
        ops = _random_chain(rng)
        _assert_partials_equal(apply_ops(ops, a, fused),
                               apply_ops(ops, a, unfused))


def test_fuse_chain_recognition():
    fusible = [Filter(col(1) > lit(5)), KeyBy(col(0)),
               Aggregate("sum", col(2))]
    fc = fuse_chain(fusible)
    assert fc is not None and fc.columns == (0, 1, 2)
    assert frag_columns([op_to_spec(o) for o in fusible]) == (0, 1, 2)
    # select remaps columns back to original indices
    fc = fuse_chain([Select((2, 1)), Filter(col(1) > lit(5)),
                     Aggregate("sum", col(0))])
    assert fc is not None and fc.columns == (1, 2)
    # unfusible shapes
    assert fuse_chain([Filter(col(0) > lit(1))]) is None
    assert fuse_chain([Aggregate("histogram", col(0),
                                 vrange=(0, 1))]) is None
    assert fuse_chain([KeyBy(col(0)), Filter(col(1) > lit(0)),
                       Aggregate("sum", col(2))]) is None


def test_colblock_roundtrip_and_pruned_read(sage):
    rng = np.random.default_rng(5)
    a = np.empty((700, 3), np.int32)
    a[:] = rng.integers(-1000, 1000, a.shape)
    sage.put_columnar("cb/0", a, container="cb")
    attrs = sage.store.meta("cb/0").attrs
    assert attrs["kind"] == "colblock"
    np.testing.assert_array_equal(sage.materialize("cb/0"), a)
    batch = sage.read_columns("cb/0", [2, 0])
    assert sorted(batch.cols) == [0, 2] and batch.rows == 700
    np.testing.assert_array_equal(batch.col(2), a[:, 2])
    with pytest.raises(ValueError, match="pruned"):
        batch.to_rows()
    np.testing.assert_array_equal(batch.stack([2, 0]),
                                  a[:, [2, 0]])
    # byte accounting: two of three equal-width int32 columns
    assert column_nbytes(attrs, [0, 2]) == 2 * 700 * 4
    assert column_nbytes(attrs, None) == 3 * 700 * 4


def test_colblock_mixed_dtypes_roundtrip(sage):
    cols = [np.arange(40, dtype=np.int64),
            np.linspace(0, 1, 40, dtype=np.float32)]
    payload, attrs = encode_columns(cols)
    assert attrs["coldtypes"] == ["int64", "float32"]
    sage.put_columnar("cb/m", cols, container="cb")
    got = sage.read_columns("cb/m")
    np.testing.assert_array_equal(got.col(0), cols[0])
    np.testing.assert_array_equal(got.col(1), cols[1])
    assert got.to_rows().dtype == np.float64   # promoted


def test_compaction_emits_colblock_and_stays_byte_identical(sage):
    from repro.compaction.compactor import CompactionPolicy
    comp = sage.compaction()
    comp.compactor.policy = CompactionPolicy(small_bytes=1 << 20)
    rng = np.random.default_rng(11)
    want = []
    for _ in range(5):
        rows = rng.integers(-500, 500, (97, 3)).astype(np.int32)
        comp.append_rows("tbl", rows)
        want.append(rows)
    comp.compact("tbl")
    entries = comp.manifest("tbl").snapshot().entries
    kinds = {sage.store.meta(e.oid).attrs.get("kind") for e in entries}
    assert kinds == {"colblock"}
    np.testing.assert_array_equal(comp.read_rows("tbl"), np.vstack(want))
    np.testing.assert_array_equal(comp.read_rows("tbl", columns=[1]),
                                  np.vstack(want)[:, [1]])


def test_prunable_columns_respects_dtype_guards():
    spec = [op_to_spec(o) for o in
            [Filter(col(0) >= lit(50)), KeyBy(col(1)),
             Aggregate("sum", col(0))]]
    attrs = {"kind": "colblock", "shape": [10, 2],
             "coldtypes": ["int32", "int32"]}
    assert prunable_columns(spec, attrs) == (0, 1)
    # scalar float sum can't fuse -> must not prune
    scalar = [op_to_spec(o) for o in
              [Filter(col(0) >= lit(50)), Aggregate("sum", col(0))]]
    f_attrs = {"kind": "colblock", "shape": [10, 2],
               "coldtypes": ["float32", "int32"]}
    assert prunable_columns(scalar, f_attrs) is None
    assert prunable_columns(scalar, {"kind": "array"}) is None


def _colblock_events(sage, n_objects=4, rows=320, seed=2):
    rng = np.random.default_rng(seed)
    arrs = []
    for i in range(n_objects):
        a = np.empty((rows, 4), np.int32)
        a[:, 0] = rng.integers(0, 8, rows)
        a[:, 1] = rng.integers(50, 100, rows) if i % 2 == 0 \
            else rng.integers(0, 50, rows)
        a[:, 2] = rng.integers(-40, 40, rows)
        a[:, 3] = i
        sage.put_columnar(f"ev/{i:02d}", a, container="ev")
        arrs.append(a)
    return np.vstack(arrs)


def test_executor_pruned_ship_parity_and_counters(sage):
    allr = _colblock_events(sage)
    eng = sage.analytics(interpret=True, partial_cache_size=0)
    try:
        q = (eng.scan("ev").filter(col(1) >= 50).key_by(col(0))
             .aggregate("sum", col(2)))
        r1 = eng.run(q)          # first run piggybacks stats (full reads)
        r2 = eng.run(q)
        # second run has fresh stats; shipped partitions prune to the
        # 3 referenced columns of 4
        shipped = [o for o, m in r2.stats.decisions.items() if m == "ship"]
        assert r2.stats.pruned_reads == len(shipped) > 0
        m = allr[allr[:, 1] >= 50]
        wk = np.unique(m[:, 0])
        wv = np.array([m[m[:, 0] == k][:, 2].sum() for k in wk])
        for r in (r1, r2):
            np.testing.assert_array_equal(r.value[0], wk)
            np.testing.assert_array_equal(r.value[1], wv)
        # pruned scan accounting: 3 of 4 columns
        full = sum(sage.store.read_size(o) for o in sage.container("ev"))
        assert 0 < r2.stats.bytes_scanned < full
    finally:
        eng.close()


def test_executor_double_buffered_fetch_parity(sage):
    from tests.conftest import make_events
    allr = make_events(sage, n_objects=6, rows=128)
    eng = sage.analytics(pushdown=False, interpret=True)
    try:
        q = (eng.scan("events").filter(col(1) >= 50).key_by(col(0))
             .aggregate("sum", col(2)))
        r = eng.run(q)
        assert r.stats.double_buffered == 6
        m = allr[allr[:, 1] >= 50]
        wk = np.unique(m[:, 0])
        wv = np.array([m[m[:, 0] == k][:, 2].sum() for k in wk])
        np.testing.assert_array_equal(r.value[0], wk)
        np.testing.assert_array_equal(r.value[1], wv)
    finally:
        eng.close()


@pytest.mark.parametrize("pushdown", [True, False])
def test_mixed_dtype_colblock_int_sums_exact_on_every_path(sage, pushdown):
    """An int32 column next to a float32 one: the first shipped query
    (which piggybacks a stats refresh) and the fetch path must both fold
    the int32 values in int32.  Rows stacked to a promoted float64 array
    reach the kernel as floats and fold in float32, which is inexact once
    a group's sum passes 2**24."""
    rng = np.random.default_rng(11)
    rows = 40000
    key = rng.integers(0, 2, rows).astype(np.int32)
    val = rng.integers(900, 1000, rows).astype(np.int32)
    fval = rng.random(rows).astype(np.float32)
    sage.put_columnar("mx/0", [key, val, fval], container="mx")
    eng = sage.analytics(pushdown=pushdown)
    try:
        r = eng.run(eng.scan("mx").key_by(col(0)).aggregate("sum", col(1)))
        want = np.bincount(key, weights=val.astype(np.float64))
        assert want.max() > 2 ** 24
        np.testing.assert_array_equal(r.value[0], [0, 1])
        assert r.value[1].dtype == np.int32
        np.testing.assert_array_equal(r.value[1].astype(np.int64),
                                      want.astype(np.int64))
    finally:
        eng.close()


def test_kernel_closure_cache_reuse():
    K.kernel_cache_clear()
    rng = np.random.default_rng(9)
    c0 = rng.integers(0, 100, 256).astype(np.int32)
    ids = rng.integers(0, 4, 256).astype(np.int32)
    K.fused_filter_aggregate({0: c0}, PRED, VAL, ids, 4, op="sum",
                             interpret=True)
    before = K.kernel_cache_info()
    K.fused_filter_aggregate({0: c0}, PRED, VAL, ids, 4, op="sum",
                             interpret=True)
    after = K.kernel_cache_info()
    assert after["hits"] > before["hits"]
    assert after["entries"] == before["entries"]


def test_histogram_selectivity_beats_uniform():
    """Within-range skew: the histogram estimate lands near the truth
    where the uniform-range model is off by an order of magnitude."""
    import dataclasses
    from repro.analytics.cost import (PartitionStats, expr_selectivity,
                                      summarize_rows)
    rng = np.random.default_rng(4)
    v = np.concatenate([rng.uniform(0, 10, 990),
                        rng.uniform(10, 1000, 10)])
    ps = PartitionStats.from_summary("o", 1, summarize_rows(v.reshape(-1, 1)))
    assert ps.cols[0].hist is not None
    pred = {"t": "bin", "op": ">", "l": {"t": "col", "i": 0},
            "r": {"t": "lit", "v": 500.0}}
    sel = expr_selectivity(pred, ps, [0])
    truth = float((v > 500).mean())
    assert abs(sel - truth) < 0.05
    # strip the histogram: the uniform-range fallback is ~50% — off by
    # two orders of magnitude on this skew
    bare = dataclasses.replace(
        ps, cols=[dataclasses.replace(ps.cols[0], hist=None)])
    uni = expr_selectivity(pred, bare, [0])
    assert abs(uni - truth) > 0.3
