"""Peak table, operation and byte counts, and the expression language."""
import numpy as np
import pytest

from bench import peaks
from bench.flops import mamba2_train_flops_per_token
from bench.ref import expr as bx


def test_known_kind_loads():
    p = peaks.load("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.load("cpu")


def test_mamba2_flops_per_token():
    cfg = {"d_model": 4, "expand": 2, "headdim": 4, "ngroups": 1,
           "d_state": 2, "n_layers": 3, "vocab_size": 10, "d_conv": 4,
           "chunk_size": 3}
    # di 8, heads 2, proj 2*8 + 2*2 + 2 = 22, conv dim 12, pairs (3+1)/2
    layer = (2 * 4 * 22 + 2 * 4 * 12 + 2 * 2 * 2 * 1
             + 2 * (2 * 2 * 4 + 4 * 2 * 4) + 2 * 8 * 4)
    assert mamba2_train_flops_per_token(cfg) == 3 * (3 * layer + 2 * 4 * 10)
    full = {"d_model": 768, "expand": 2, "headdim": 64, "ngroups": 1,
            "d_state": 128, "n_layers": 24, "vocab_size": 50277,
            "pad_vocab_size_multiple": 16, "d_conv": 4, "chunk_size": 256}
    assert 0.8e9 < mamba2_train_flops_per_token(full) < 0.9e9


def test_query_logical_cost():
    from bench.drivers.query import logical_cost
    q = {"filter": ["<=", "a", 5], "group": ["+", ["*", "b", 2], "c"],
         "aggregates": [{"agg": "sum", "value": ["*", "d", ["-", 1.0, "e"]]},
                        {"agg": "mean", "value": "d"}, {"agg": "count"}]}
    # columns a..e once and one int32 id a row; 1 + 2 + 2 expression
    # ops and a fold for each of the 3 aggregates
    assert logical_cost(q, 10) == (10 * 4 * 6, 10 * 8)
    one = dict(q, aggregates=[{"agg": "count"}])
    assert logical_cost(one, 10) == (10 * 4 * 4, 10 * 4)


def test_least_seconds_takes_the_longer_bound():
    p = peaks.load("TPU v5 lite")
    assert peaks.least_seconds(819e9, 0, p) == 1.0
    assert peaks.least_seconds(0, 197e12 * 2, p) == 2.0
    assert peaks.least_seconds(819e9, 197e12 * 2, p) == 2.0


def test_expressions_resolve_and_evaluate():
    e = bx.resolve(["<=", "ship", ["-", {"date": "1998-12-01"}, "$delta"]],
                   {"delta": 90})
    assert e == ["<=", "ship", 10561 - 90]
    cols = {"ship": np.array([10470, 10471, 10472])}
    assert list(bx.evaluate(e, cols.__getitem__)) == [True, True, False]
    assert bx.columns(e) == {"ship"} and bx.count_ops(e) == 1
    with pytest.raises(ValueError):
        bx.resolve(["^", 1, 2], {})


def test_stream_parameters():
    from bench.drivers.query import draw_params
    spec = {"delta": {"uniform_int": [60, 61], "distinct": True},
            "k": {"uniform_int": [1, 3]}}
    a = draw_params(spec, 2, 2 ** 33 + 1)
    assert a == draw_params(spec, 2, 2 ** 33 + 1)
    assert sorted(p["delta"] for p in a) == [60, 61]
    assert all(1 <= p["k"] <= 3 for p in a)
