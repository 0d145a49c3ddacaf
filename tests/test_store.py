"""SAGE object store / Clovis tests: layouts, transactions, HA, HSM,
function shipping.  Hypothesis property tests on the KV index and
block-round-trip invariants live in test_store_properties.py (skipped
when hypothesis is absent)."""
import json
import time

import numpy as np
import pytest

from repro.core import (Clovis, FailureEvent, FunctionShipper, HAMonitor,
                        HsmDaemon, Layout, recommend_tier)
from repro.core import layouts as lay
from repro.core.tiers import T1_NVRAM, T2_FLASH, T4_ARCHIVE


# ---------------------------------------------------------------------------
# objects & layouts
# ---------------------------------------------------------------------------

def test_block_roundtrip_and_checksums(sage):
    sage.create("o/1", block_size=256)
    data = bytes(range(256)) * 5            # 5 blocks
    sage.put("o/1", data)
    assert sage.get("o/1") == data
    meta = sage.store.meta("o/1")
    assert meta.nblocks == 5 and len(meta.checksums) == 5


def test_block_size_must_be_pow2(sage):
    with pytest.raises(ValueError):
        sage.create("o/bad", block_size=300)


def test_partial_overwrite_preserves_other_blocks(sage):
    sage.create("o/2", block_size=256)
    sage.put("o/2", b"A" * 1024)
    sage.store.write("o/2", b"B" * 256, start_block=2)
    out = sage.store.read("o/2")
    assert out[:512] == b"A" * 512
    assert out[512:768] == b"B" * 256
    assert out[768:1024] == b"A" * 256


def test_mirrored_survives_single_device_failure(sage):
    sage.create("o/m", block_size=128,
                layout=Layout(lay.MIRRORED, T2_FLASH, 2))
    sage.put("o/m", b"x" * 1000)
    sage.pools[T2_FLASH].devices[0].fail()
    assert sage.get("o/m") == b"x" * 1000


def test_parity_rebuild_after_device_loss(sage):
    sage.create("o/p", block_size=128,
                layout=Layout(lay.PARITY, T4_ARCHIVE, 2))
    data = bytes([i % 251 for i in range(128 * 4)])
    sage.put("o/p", data)
    sage.pools[T4_ARCHIVE].devices[0].fail()
    assert sage.get("o/p") == data


def test_striped_loses_data_on_failure(sage):
    """RAID-0 semantics: striped layouts tolerate zero failures."""
    sage.create("o/s", block_size=128,
                layout=Layout(lay.STRIPED, T2_FLASH, 2))
    sage.put("o/s", b"y" * 512)
    for d in sage.pools[T2_FLASH].devices:
        d.fail()
    with pytest.raises(IOError):
        sage.get("o/s")


# ---------------------------------------------------------------------------
# the read path: blocks land in the caller's buffer
# ---------------------------------------------------------------------------

def _joined_blocks(store, oid):
    meta = store.meta(oid)
    buf = memoryview(bytearray(meta.block_size))
    return b"".join(
        bytes(buf[:store._read_block(meta, i, meta.version, buf,
                                     record=False)])
        for i in range(meta.nblocks))


def _replica_file(store, oid, idx, r=0):
    meta = store.meta(oid)
    dev, key = store._placements(meta, idx, meta.version)[r]
    return dev, dev.root / key


@pytest.mark.parametrize("kind,tier", [(lay.STRIPED, T2_FLASH),
                                       (lay.MIRRORED, T2_FLASH),
                                       (lay.PARITY, T4_ARCHIVE)])
@pytest.mark.parametrize("size", [256 * 5, 256 * 4 + 100])
def test_read_into_matches_block_by_block_read(sage, kind, tier, size):
    sage.create("r/x", block_size=256, layout=Layout(kind, tier, 2))
    data = bytes((i * 7) % 251 for i in range(size))
    sage.put("r/x", data)
    store = sage.store
    nblocks = store.meta("r/x").nblocks
    assert _joined_blocks(store, "r/x") == data
    assert store.read_counters()["direct_blocks"] == nblocks
    gets = len(sage.addb.records("get"))
    assert store.read("r/x") == data
    assert len(sage.addb.records("get")) == gets + nblocks
    buf = bytearray(nblocks * 256)
    assert store.read_into("r/x", 0, nblocks, buf) == size
    assert bytes(buf[:size]) == data
    arr = np.zeros(nblocks * 256, np.uint8)
    assert store.read_into("r/x", 0, None, arr) == size
    assert arr[:size].tobytes() == data
    # a ranged read that ends in the short block
    assert store.read("r/x", 3) == data[3 * 256:]
    assert len(sage.addb.records("get")) == gets + 3 * nblocks + (nblocks - 3)
    assert store.read_counters() == {
        "direct_blocks": 4 * nblocks + nblocks - 3, "fallback_blocks": 0}
    with pytest.raises(ValueError):
        store.read_into("r/x", 0, nblocks, bytearray(nblocks * 256 - 1))


@pytest.mark.parametrize("fault", ["failed_device", "corrupt_replica",
                                   "missing_directory"])
def test_mirrored_read_falls_back_to_other_replica(sage, fault):
    import shutil
    sage.create("r/m", block_size=128,
                layout=Layout(lay.MIRRORED, T2_FLASH, 2))
    data = bytes(range(256)) * 3 + b"tail"             # 7 blocks, last short
    sage.put("r/m", data)
    dev, path = _replica_file(sage.store, "r/m", 2)
    if fault == "failed_device":
        dev.fail()
    elif fault == "corrupt_replica":
        path.write_bytes(b"\xff" * 128)
    else:
        shutil.rmtree(path.parent)
    assert sage.store.read("r/m") == data
    counts = sage.store.read_counters()
    assert counts["fallback_blocks"] >= 1
    assert counts["direct_blocks"] + counts["fallback_blocks"] == 7
    if fault == "corrupt_replica":
        assert counts["fallback_blocks"] == 1
    if fault == "missing_directory":
        assert not path.parent.exists()         # reads create no directory


def test_get_latency_leaves_out_the_checksum(sage, monkeypatch):
    """HA's straggler report compares the ADDB ``get`` latencies with the
    tier's model latency, so they time the device read alone."""
    import zlib
    from repro.core import object_store
    sage.create("r/t", block_size=128)
    sage.put("r/t", b"q" * 384)
    crc32 = zlib.crc32

    def slow_crc32(data):
        time.sleep(0.05)
        return crc32(data)

    monkeypatch.setattr(object_store.zlib, "crc32", slow_crc32)
    gets = len(sage.addb.records("get"))
    assert sage.store.read("r/t") == b"q" * 384
    recs = sage.addb.records("get")[gets:]
    assert len(recs) == 3
    assert all(r.latency_s < 0.05 for r in recs)


def test_corrupt_block_without_replica_raises(sage):
    sage.create("r/s", block_size=128,
                layout=Layout(lay.STRIPED, T2_FLASH, 2))
    sage.put("r/s", b"z" * 512)
    _, path = _replica_file(sage.store, "r/s", 1)
    path.write_bytes(b"y" * 128)
    with pytest.raises(IOError):
        sage.store.read("r/s")
    with pytest.raises(IOError):
        sage.store.read_into("r/s", 0, 4, bytearray(512))
    # a block file longer than its block is refused on the direct path
    path.write_bytes(b"z" * 129)
    with pytest.raises(IOError):
        sage.store.read("r/s")


def test_read_columns_land_in_one_buffer_per_column(sage):
    rng = np.random.default_rng(3)
    rows = 3000                               # 4 KiB blocks: 3 each for 4 B
    cols = [rng.integers(-9, 9, rows).astype(np.int32),
            rng.standard_normal(rows).astype(np.float32),
            rng.standard_normal(rows),        # float64: 6 blocks
            rng.integers(0, 255, rows).astype(np.uint8)]
    sage.put_columnar("cb/0", cols)
    attrs = sage.store.meta("cb/0").attrs
    before = sage.store.read_counters()
    batch = sage.read_columns("cb/0", [2, 0])
    after = sage.store.read_counters()
    want_blocks = sum(attrs["colblocks"][c][1] for c in (2, 0))
    assert after["direct_blocks"] - before["direct_blocks"] == want_blocks
    assert after["fallback_blocks"] == before["fallback_blocks"] == 0
    assert sorted(batch.cols) == [0, 2]
    for c in (0, 2):
        got = batch.col(c)
        # the ranged read as bytes, trimmed to the rows
        start, nb = attrs["colblocks"][c]
        old = np.frombuffer(sage.store.read("cb/0", start, nb),
                            dtype=cols[c].dtype)[:rows].copy()
        assert got.dtype == old.dtype == cols[c].dtype
        assert got.shape == (rows,)
        np.testing.assert_array_equal(got, old)
        np.testing.assert_array_equal(got, cols[c])
        assert got.flags.writeable
        got[0] = 1                            # a private buffer
    np.testing.assert_array_equal(sage.read_columns("cb/0", [0]).col(0),
                                  cols[0])
    # a long column is a view of its blocks; a column in less than half
    # of its one block is copied out, so it does not pin the padding
    assert batch.col(2).base is not None
    short = rng.standard_normal(10).astype(np.float32)
    sage.put_columnar("cb/1", [short])
    got = sage.read_columns("cb/1", [0]).col(0)
    np.testing.assert_array_equal(got, short)
    assert got.base is None and got.flags.writeable


def test_concurrent_reads_count_every_block(sage):
    """More reader threads than cores, switching often: every read is
    whole and no block goes uncounted."""
    import sys
    from concurrent.futures import ThreadPoolExecutor
    data = np.arange(16 * 1024, dtype=np.int32).tobytes()   # 16 blocks
    sage.create("cc/x", block_size=4096)
    sage.put("cc/x", data)
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(32) as ex:
            outs = list(ex.map(lambda _: sage.store.read("cc/x"),
                               range(200), timeout=60))
    finally:
        sys.setswitchinterval(prev)
    assert all(o == data for o in outs)
    assert sage.store.read_counters() == {"direct_blocks": 200 * 16,
                                          "fallback_blocks": 0}


def test_containers_group_objects(sage):
    sage.create("a/1", container="c1")
    sage.create("a/2", container="c1")
    sage.create("b/1", container="c2")
    assert sage.container("c1") == ["a/1", "a/2"]
    assert sage.container("c2") == ["b/1"]


# ---------------------------------------------------------------------------
# transactions
# ---------------------------------------------------------------------------

def test_txn_commit_flips_version_atomically(sage):
    sage.create("t/1", block_size=256)
    sage.put("t/1", b"old" * 100)
    with sage.transaction(["t/1"]) as txn:
        sage.put("t/1", b"new" * 100, txn=txn)
        # inside the txn the old version is still what readers see
        assert sage.get("t/1") == b"old" * 100
    assert sage.get("t/1") == b"new" * 100


def test_txn_abort_leaves_previous_state(sage):
    sage.create("t/2", block_size=256)
    sage.put("t/2", b"keep" * 64)
    with pytest.raises(RuntimeError):
        with sage.transaction(["t/2"]) as txn:
            sage.put("t/2", b"gone" * 64, txn=txn)
            raise RuntimeError("crash mid-transaction")
    assert sage.get("t/2") == b"keep" * 64


def test_wal_recovery_garbage_collects_orphans(sage, tmp_path):
    from repro.core.clovis import Clovis

    sage.create("t/3", block_size=256)
    sage.put("t/3", b"base" * 64)
    # simulate crash: intent logged, blocks written, no commit record
    txn = sage.transaction(["t/3"])
    txn.__enter__()
    sage.store.write("t/3", b"crashx" * 50, txn=txn)
    # (no __exit__: process died)
    incomplete = sage.store.txn_mgr.incomplete()
    assert len(incomplete) == 1
    n = sage.store.recover()
    assert n == 1
    assert sage.get("t/3") == b"base" * 64


# ---------------------------------------------------------------------------
# HA
# ---------------------------------------------------------------------------

def test_ha_threshold_digestion(sage):
    ha = HAMonitor(sage.store, error_threshold=3, window_s=60)
    sage.create("h/1", block_size=128,
                layout=Layout(lay.MIRRORED, T2_FLASH, 2))
    sage.put("h/1", b"q" * 512)
    dev = sage.pools[T2_FLASH].devices[1]
    import time
    for _ in range(2):
        ha.observe(FailureEvent(time.time(), "io_error", dev.name))
    assert dev.name not in ha.evicted          # below threshold
    ha.observe(FailureEvent(time.time(), "io_error", dev.name))
    assert dev.name in ha.evicted              # digested -> repaired
    assert sage.get("h/1") == b"q" * 512


def test_ha_repair_restores_redundancy(sage):
    ha = HAMonitor(sage.store)
    sage.create("h/2", block_size=128,
                layout=Layout(lay.MIRRORED, T1_NVRAM, 2))
    sage.put("h/2", b"r" * 640)
    d0 = sage.pools[T1_NVRAM].devices[0]
    ha.engage_repair(d0.name)
    # second failure after repair must still be survivable
    sage.pools[T1_NVRAM].devices[1].fail()
    assert sage.get("h/2") == b"r" * 640


# ---------------------------------------------------------------------------
# HSM / RTHMS
# ---------------------------------------------------------------------------

def test_hsm_promotes_hot_demotes_cold(sage):
    hsm = HsmDaemon(sage.store)
    sage.put_array("hot/x", np.ones(100, np.float32),
                   layout=Layout(lay.STRIPED, T2_FLASH, 2))
    for _ in range(3):
        sage.get_array("hot/x")
    hsm.scan_once()
    assert sage.store.meta("hot/x").layout.tier == T1_NVRAM
    # force cold: fake old last_access
    sage.store.meta("hot/x").last_access -= 10_000
    sage.store.meta("hot/x").access_count = 0
    hsm.scan_once()
    assert sage.store.meta("hot/x").layout.tier == T2_FLASH


def test_reads_during_migration_never_fail(sage):
    """A migration (HSM, prefetch staging) publishes its new version only
    once every block has landed, so concurrent readers never see a
    version whose blocks are missing."""
    import sys
    import threading
    data = np.arange(64 * 1024, dtype=np.int32)     # 64 blocks of 4 KiB
    sage.create("mig/x", block_size=4096)
    sage.put("mig/x", data.tobytes())
    stop = threading.Event()
    migrations = []

    def migrate():
        tiers = (T1_NVRAM, T2_FLASH)
        while not stop.is_set() and len(migrations) < 200:
            t = tiers[len(migrations) % 2]
            sage.migrate("mig/x", Layout(lay.MIRRORED, t, 2))
            migrations.append(t)

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    mover = threading.Thread(target=migrate, daemon=True)
    try:
        mover.start()
        for _ in range(100):
            assert sage.get("mig/x") == data.tobytes()
    finally:
        stop.set()
        mover.join(timeout=30)
        sys.setswitchinterval(prev)
    assert not mover.is_alive() and len(migrations) > 1


def test_rthms_recommendation_prefers_fast_tier_for_random(sage):
    tier = recommend_tier(sage.store, size_bytes=1 << 20,
                          read_fraction=0.9, random_access=True)
    assert tier == T1_NVRAM
    tier2 = recommend_tier(sage.store, size_bytes=1 << 20,
                           read_fraction=0.5, random_access=False,
                           exclude=(T1_NVRAM,))
    assert tier2 == T2_FLASH


# ---------------------------------------------------------------------------
# function shipping
# ---------------------------------------------------------------------------

def test_function_shipping_reductions(sage):
    x = np.arange(64, dtype=np.float32)
    sage.put_array("f/x", x)
    sh = FunctionShipper(sage)
    assert abs(sh.ship("sum", "f/x").value - x.sum()) < 1e-3
    assert abs(sh.ship("l2norm", "f/x").value -
               np.linalg.norm(x)) < 1e-2
    res = sh.ship("quantize_int8", "f/x")
    assert res.ok and res.value["int8"].dtype == np.int8
    bad = sh.ship("nonexistent", "f/x")
    assert not bad.ok
    sh.shutdown()


def test_ship_to_container(sage):
    for i in range(4):
        sage.put_array(f"c/{i}", np.full(8, i, np.float32),
                       container="ship")
    sh = FunctionShipper(sage)
    results = sh.ship_to_container("mean", "ship")
    assert sorted(round(r.value) for r in results) == [0, 1, 2, 3]
    sh.shutdown()


# ---------------------------------------------------------------------------
# FDMI plugins
# ---------------------------------------------------------------------------

def test_fdmi_plugins(sage):
    from repro.core.fdmi import CompressionPlugin, IndexingPlugin, IntegrityPlugin

    integ = IntegrityPlugin(sage)
    comp = CompressionPlugin(sage)
    idx = IndexingPlugin(sage)
    sage.create("p/1", block_size=256, container="plug")
    sage.put("p/1", b"\x00" * 2048)
    assert comp.ratios.get("p/1", 0) > 10        # zeros compress well
    assert integ.scrub("plug") == []
    assert len(idx.index) >= 1
