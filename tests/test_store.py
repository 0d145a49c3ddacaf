"""SAGE object store / Clovis tests: layouts, transactions, HA, HSM,
function shipping.  Hypothesis property tests on the KV index and
block-round-trip invariants live in test_store_properties.py (skipped
when hypothesis is absent)."""
import json

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
