"""The TPC-H lineitem generator and the training cell's weights and
corpus: determinism per seed and the spec's rules."""
import numpy as np
import pytest

from bench.gen import lineitem
from bench.tests import cells

CFG = dict(cells.load(cells.BENCH / "configs" / "tpch-lineitem-sf5.json"),
           scale_factor=0.01)


@pytest.fixture(scope="module")
def table():
    return lineitem.generate(CFG, 2 ** 31 + 11)


def test_same_seed_same_table(table):
    again = lineitem.generate(CFG, 2 ** 31 + 11)
    other = lineitem.generate(CFG, 2 ** 31 + 12)
    assert all(np.array_equal(table[k], again[k]) for k in table)
    assert not np.array_equal(table["l_shipdate"], other["l_shipdate"])


def test_rows_at_scale(table):
    orders = 0.01 * lineitem.ORDERS_PER_SF
    n = len(table["l_orderkey"])
    # 1..7 lines per order, 4 on average: within 5 standard deviations
    assert abs(n - 4 * orders) < 5 * 2 * np.sqrt(orders)
    assert len(np.unique(table["l_orderkey"])) == orders
    assert [c["name"] for c in CFG["columns"]] == list(table)
    assert all(len(v) == n for v in table.values())
    # dbgen's sparse order keys: the first 8 of every 32
    assert np.all((table["l_orderkey"] - 1) % 32 < 8)


def test_dates_flags_and_prices(table):
    day = lineitem.day_number
    ship, commit, receipt = (table[k] for k in ("l_shipdate", "l_commitdate",
                                                "l_receiptdate"))
    assert day("1992-01-02") <= ship.min() and ship.max() <= day(
        "1998-08-02") + 121
    assert np.all((receipt - ship >= 1) & (receipt - ship <= 30))
    current = day("1995-06-17")
    codes = CFG["codes"]
    status = np.array(codes["l_linestatus"])[table["l_linestatus"]]
    assert np.all((status == "O") == (ship > current))
    flag = np.array(codes["l_returnflag"])[table["l_returnflag"]]
    assert np.all((flag == "N") == (receipt > current))
    assert set(np.unique(flag[receipt <= current])) == {"A", "R"}
    q = table["l_quantity"]
    assert q.min() == 1 and q.max() == 50
    assert set(np.unique(np.rint(table["l_discount"] * 100))) == set(
        range(11))
    want = q * lineitem.retail_price(table["l_partkey"])
    np.testing.assert_allclose(table["l_extendedprice"], want, rtol=1e-6)


def test_q1_has_four_groups(table):
    f, s = table["l_returnflag"], table["l_linestatus"]
    keep = table["l_shipdate"] <= lineitem.day_number("1998-12-01") - 90
    assert len(np.unique((f * 2 + s)[keep])) == 4


def test_supplier_key_rule(table):
    sf = CFG["scale_factor"]
    S = int(sf * lineitem.SUPPLIERS_PER_SF)
    pk, sk = table["l_partkey"], table["l_suppkey"]
    four = np.stack([lineitem.supplier_key(pk, np.int64(i), S)
                     for i in range(4)])
    assert np.all((four == sk).any(axis=0))
    assert sk.min() >= 1 and sk.max() <= S
    # dbgen's worked example: part 1 of SF1 has suppliers 2, 2502, 5002, 7502
    assert [int(lineitem.supplier_key(np.array([1]), np.int64(i), 10000)[0])
            for i in range(4)] == [2, 2502, 5002, 7502]


def test_weights_and_corpus_follow_the_seed():
    import jax
    from bench.gen import mamba2 as gen
    cfg = dict(cells.load(cells.BENCH / "configs" / "mamba2-130m.json"),
               n_layers=2, d_model=64, d_state=16, headdim=16,
               chunk_size=16, vocab_size=256)
    a, b = gen.init_weights(cfg, 7), gen.init_weights(cfg, 7)
    c = gen.init_weights(cfg, 7 + (1 << 32))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["in_proj"], c["in_proj"])
    assert a["in_proj"].shape == (2, 64, 2 * 128 + 2 * 16 + 8)
    shards = gen.make_corpus(cfg, 7, 2, 500)
    assert np.array_equal(shards[1], gen.make_corpus(cfg, 7, 2, 500)[1])
    idx = gen.CorpusIndex(shards)
    assert np.array_equal(idx.locate(shards[1][100:300]), shards[1][100:300])
    with pytest.raises(LookupError):
        idx.locate(np.arange(20, dtype=np.int32) + 3)
    assert a["embed"].shape == (256, 64) and shards[0].max() < 256
    # the program tree holds the same arrays under the program's names,
    # layers scanned as one stack or unrolled one by one
    tree = gen.to_program(a, scan_layers=True)
    names = gen.program_names(tree)
    assert sorted(set(names)) == sorted(a)
    for name, leaf in zip(names, jax.tree.leaves(tree)):
        assert np.array_equal(leaf, a[name])
    tree = gen.to_program(a, scan_layers=False)
    names = gen.program_names(tree)
    assert "in_proj.1" in names and len(names) == 2 + 2 * 9
    for name, leaf in zip(names, jax.tree.leaves(tree)):
        assert np.array_equal(leaf, gen.leaf_of(a, name))


def test_embedding_rows_pad_the_vocabulary():
    from bench.gen import mamba2 as gen
    assert gen.padded_vocab({"vocab_size": 50277,
                             "pad_vocab_size_multiple": 16}) == 50288
    assert gen.padded_vocab({"vocab_size": 256,
                             "pad_vocab_size_multiple": 16}) == 256
    assert gen.padded_vocab({"vocab_size": 50277}) == 50277
