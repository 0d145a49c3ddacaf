"""Percipience subsystem tests: heat kernel vs numpy reference, Markov
prediction, prefetch hit-rate vs the reactive baseline, byte budget,
ADDB windowed arrays, and pluggable HSM scoring."""
import time

import numpy as np
import pytest

from repro.core import CountingScorer, HsmDaemon, Layout
from repro.core import layouts as lay
from repro.core.hsm import DEMOTE, PROMOTE
from repro.core.tiers import T1_NVRAM, T2_FLASH, T3_DISK
from repro.percipience import (FeatureExtractor, PercipientPolicy,
                               Prefetcher, attach_percipience, heat_scores,
                               markov_predict)
from repro.percipience.heat import heat_scores_ref

FAST = (T1_NVRAM, T2_FLASH)


# ---------------------------------------------------------------------------
# heat kernel
# ---------------------------------------------------------------------------

def test_heat_kernel_matches_numpy_reference(rng):
    n, L = 37, 24                       # deliberately off tile multiples
    now = time.time()
    ts = np.sort(now - rng.uniform(0, 900, (n, L)), axis=1)
    mask = np.ones((n, L))
    for i in range(n):                  # variable-length histories
        k = int(rng.integers(0, L + 1))
        mask[i, :L - k] = 0.0
        ts[i, :L - k] = 0.0
    got = heat_scores(ts, mask, now, half_life_s=120.0, interpret=True)
    want = heat_scores_ref(ts, mask, now, half_life_s=120.0)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_heat_kernel_weighted_and_empty(rng):
    now = time.time()
    ts = np.array([[now - 10, now - 5], [0.0, 0.0]])
    mask = np.array([[1.0, 1.0], [0.0, 0.0]])
    w = np.array([[2.0, 3.0], [1.0, 1.0]])
    got = heat_scores(ts, mask, now, 60.0, weights=w, interpret=True)
    want = heat_scores_ref(ts, mask, now, 60.0, weights=w)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    assert got[1] == 0.0                # no accesses -> zero heat
    assert heat_scores(np.zeros((0, 4)), np.zeros((0, 4)), now,
                       interpret=True).shape == (0,)


def test_heat_decays_over_time():
    now = time.time()
    ts = np.array([[now - 1.0]])
    mask = np.ones((1, 1))
    fresh = heat_scores(ts, mask, now, half_life_s=10.0, interpret=True)[0]
    stale = heat_scores(ts, mask, now + 100.0, half_life_s=10.0,
                        interpret=True)[0]
    assert fresh > 0.9 and stale < 1e-3


# ---------------------------------------------------------------------------
# Markov predictor
# ---------------------------------------------------------------------------

def test_markov_predictor_learns_repeating_trace():
    ex = FeatureExtractor(max_objects=8)
    cycle = ["a", "b", "c", "d"]
    for _ in range(5):
        for oid in cycle:
            ex.on_read(oid, 100)
    probs = ex.transition_matrix()
    correct = 0
    for i, oid in enumerate(cycle):
        nxt = cycle[(i + 1) % len(cycle)]
        preds = markov_predict(probs, ex.bucket_of(oid), k=1)
        assert preds, f"no prediction for {oid}"
        if preds[0][0] == ex.bucket_of(nxt):
            correct += 1
    assert correct == len(cycle)        # 100% on a deterministic cycle
    assert preds[0][1] > 0.9            # and confident


def test_markov_zero_row_predicts_nothing():
    probs = np.zeros((4, 4))
    assert markov_predict(probs, 2, k=3, min_p=0.01) == []


# ---------------------------------------------------------------------------
# feature extractor
# ---------------------------------------------------------------------------

def test_extractor_history_tensors_and_gaps(sage):
    ex = FeatureExtractor(hist_len=8).attach(sage.store)
    sage.create("t/a", block_size=256)
    sage.put("t/a", b"z" * 1024)
    sage.get("t/a")
    oids, ts, sz, mask = ex.history_tensors()
    assert "t/a" in oids
    i = oids.index("t/a")
    assert mask[i].sum() >= 1
    assert (ts[i][mask[i] > 0] > 0).all()
    assert sz[i][mask[i] > 0].sum() > 0
    _, gaps, gmask = ex.inter_arrival_gaps()
    assert gaps.shape == ts.shape and (gaps >= 0).all()


def test_extractor_coalesces_block_fanout(sage):
    """One multi-block read lands as one access, not one per block/replica."""
    ex = FeatureExtractor(hist_len=16).attach(sage.store)
    sage.create("t/b", block_size=256)
    sage.put("t/b", b"q" * 2048)        # 8 blocks
    before = ex.access_count("t/b")
    sage.get("t/b")
    assert ex.access_count("t/b") - before <= 2


# ---------------------------------------------------------------------------
# HSM pluggable scoring
# ---------------------------------------------------------------------------

def test_hsm_default_scoring_unchanged(sage):
    """Regression: the extracted CountingScorer reproduces the daemon's
    historical promote-hot / demote-cold behaviour."""
    hsm = HsmDaemon(sage.store)
    assert isinstance(hsm.scorer, CountingScorer)
    sage.put_array("hot/x", np.ones(100, np.float32),
                   layout=Layout(lay.STRIPED, T2_FLASH, 2))
    for _ in range(3):
        sage.get_array("hot/x")
    hsm.scan_once()
    assert sage.store.meta("hot/x").layout.tier == T1_NVRAM
    sage.store.meta("hot/x").last_access -= 10_000
    sage.store.meta("hot/x").access_count = 0
    hsm.scan_once()
    assert sage.store.meta("hot/x").layout.tier == T2_FLASH


def test_hsm_scorer_hook_overrides_decisions(sage):
    class Never:
        def decide(self, meta, now):
            return None

    class AlwaysDemote:
        def decide(self, meta, now):
            return DEMOTE

    sage.put_array("s/x", np.ones(10, np.float32),
                   layout=Layout(lay.STRIPED, T2_FLASH, 2))
    for _ in range(5):
        sage.get_array("s/x")           # hot by counting standards
    assert HsmDaemon(sage.store, scorer=Never()).scan_once() == 0
    assert sage.store.meta("s/x").layout.tier == T2_FLASH
    HsmDaemon(sage.store, scorer=AlwaysDemote()).scan_once()
    assert sage.store.meta("s/x").layout.tier == T3_DISK


def test_swallowed_advisory_errors_are_counted(sage):
    """Percipience is advisory: a failing heat query or HSM scan must not
    break the caller, and each failure is counted in ADDB."""
    class Broken:
        def decide(self, meta, now):
            raise RuntimeError("scorer down")

        def heat_map(self, oids):
            raise RuntimeError("heat down")

    sage.put_array("s/x", np.ones(10, np.float32))
    sage.percipience = (None, None, Broken())
    eng = sage.analytics()
    try:
        assert eng._heat(["s/x"]) == {}
    finally:
        eng.close()
    assert sage.addb.advisory_error_count == 1
    daemon = HsmDaemon(sage.store, scorer=Broken())
    daemon.start(interval_s=0.01)
    deadline = time.time() + 10
    while sage.addb.advisory_error_count < 2 and time.time() < deadline:
        time.sleep(0.01)
    daemon.stop()
    assert sage.addb.advisory_error_count >= 2
    wheres = {r.entity for r in sage.addb.records("advisory_error")}
    assert wheres == {"analytics.heat_map", "hsm.scan"}


def test_percipient_policy_promotes_hot_demotes_stale(sage):
    ex = FeatureExtractor().attach(sage.store)
    sage.create("p/hot", block_size=256)
    sage.put("p/hot", b"h" * 1024)
    sage.create("p/cold", block_size=256)
    sage.put("p/cold", b"c" * 1024)
    for _ in range(5):
        sage.get("p/hot")
        time.sleep(0.03)                # defeat coalescing
    pol = PercipientPolicy(ex, half_life_s=60.0, promote_heat=2.0,
                           demote_heat=0.5, interpret=True)
    now = time.time()
    assert pol.decide(sage.store.meta("p/hot"), now) == PROMOTE
    # cold object: only its write is in history; far future -> heat ~ 0
    assert pol.decide(sage.store.meta("p/cold"), now + 3600) == DEMOTE


def test_watermark_eviction_ranks_victims_by_heat(sage):
    """Under watermark pressure a heat-aware scorer evicts the coldest-
    by-heat object first, even when raw LRU order disagrees."""
    from repro.core import HsmPolicy

    class HeatOnly:
        heat = {"e/cold": 0.01, "e/hot": 9.0}

        def decide(self, meta, now):
            return None                  # pressure path only

        def heat_of(self, oid, now=None):
            return self.heat.get(oid, 1.0)

    for oid in ("e/cold", "e/hot"):
        sage.put_array(oid, np.ones(64, np.float32),
                       layout=Layout(lay.STRIPED, T2_FLASH, 2))
    # LRU would pick e/hot (older last_access); heat must win instead
    sage.store.meta("e/hot").last_access -= 1_000
    hsm = HsmDaemon(sage.store, policy=HsmPolicy(high_watermark=0.0),
                    scorer=HeatOnly())
    hsm.scan_once()
    from_t2 = [oid for oid, src, _ in hsm.migrations if src == T2_FLASH]
    assert from_t2 and from_t2[0] == "e/cold"


def test_watermark_eviction_does_not_conflate_unknown_with_cold(sage):
    """A never-observed object read recently must outrank (survive) an
    observed object whose heat has decayed — PercipientPolicy.victim_rank
    scores the unknown by a single-access proxy at last_access instead
    of heat 0."""
    from repro.core import HsmPolicy

    # u/fresh exists before the extractor attaches: pre-attach traffic
    # is exactly the "never observed" case
    sage.put_array("u/fresh", np.ones(64, np.float32),
                   layout=Layout(lay.STRIPED, T2_FLASH, 2))
    ex = FeatureExtractor().attach(sage.store)
    pol = PercipientPolicy(ex, half_life_s=0.05, interpret=True)
    sage.put_array("u/observed", np.ones(64, np.float32),
                   layout=Layout(lay.STRIPED, T2_FLASH, 2))
    sage.get_array("u/observed")        # observed...
    time.sleep(0.3)                     # ...but heat fully decayed
    now = time.time()
    sage.store.meta("u/fresh").last_access = now   # recently touched
    assert ex.access_count("u/fresh") == 0
    assert pol.victim_rank(sage.store.meta("u/fresh"), now) > \
        pol.victim_rank(sage.store.meta("u/observed"), now)
    hsm = HsmDaemon(sage.store, policy=HsmPolicy(high_watermark=0.0),
                    scorer=pol)
    hsm._relieve_pressure()
    from_t2 = [oid for oid, src, _ in hsm.migrations if src == T2_FLASH]
    assert from_t2 and from_t2[0] == "u/observed"


def test_watermark_eviction_lru_fallback_without_heat(sage):
    """Scorers without heat_of keep the historical LRU victim order."""
    from repro.core import HsmPolicy

    for oid in ("l/new", "l/old"):
        sage.put_array(oid, np.ones(64, np.float32),
                       layout=Layout(lay.STRIPED, T2_FLASH, 2))
    sage.store.meta("l/old").last_access -= 1_000
    hsm = HsmDaemon(sage.store, policy=HsmPolicy(high_watermark=0.0))
    hsm.scan_once()
    from_t2 = [oid for oid, src, _ in hsm.migrations if src == T2_FLASH]
    assert from_t2 and from_t2[0] == "l/old"


# ---------------------------------------------------------------------------
# prefetcher
# ---------------------------------------------------------------------------

def _populate(sage, n, obj_bytes=4096, block=1024):
    for i in range(n):
        sage.create(f"o/{i}", block_size=block,
                    layout=Layout(lay.STRIPED, T3_DISK, 2))
        sage.put(f"o/{i}", bytes(obj_bytes))


def test_prefetcher_respects_byte_budget(sage):
    ex = FeatureExtractor().attach(sage.store)
    budget = 6000                       # fits one 4KiB object, not two
    pf = Prefetcher(sage.store, ex, byte_budget=budget, sync=True,
                    top_k=4, min_confidence=0.0).attach()
    _populate(sage, 6)
    # interleaved trace gives bucket 0 a 4-way successor fan-out, so one
    # read of o/0 tries to stage several objects at once
    for rep in range(3):
        for i in (1, 2, 3, 4):
            sage.get("o/0")
            sage.get(f"o/{i}")
    assert pf.staged_bytes <= budget
    assert pf.stats()["skipped_budget"] > 0


def test_prefetch_hit_rate_beats_reactive_on_zipf(tmp_path):
    from repro.core.addb import Addb
    from repro.core.clovis import Clovis

    rng = np.random.default_rng(7)
    n, n_reads = 24, 200
    p = 1.0 / np.arange(1, n + 1) ** 1.2
    p /= p.sum()
    trace = rng.choice(n, size=n_reads, p=p)

    def replay(mode):
        sage = Clovis(tmp_path / f"zipf_{mode}", addb=Addb(),
                      devices_per_tier=3)
        _populate(sage, n)
        if mode == "predictive":
            _, pf, policy = attach_percipience(
                sage, sync=True, byte_budget=16 << 20, top_k=3,
                min_confidence=0.05, half_life_s=60.0)
            daemon = HsmDaemon(sage.store, scorer=policy)
        else:
            daemon = HsmDaemon(sage.store)
        hits = 0
        for step, obj in enumerate(trace):
            if sage.store.meta(f"o/{obj}").layout.tier in FAST:
                hits += 1
            sage.get(f"o/{obj}")
            if (step + 1) % 16 == 0:
                daemon.scan_once()
        return hits / n_reads

    reactive, predictive = replay("reactive"), replay("predictive")
    assert predictive > reactive, (predictive, reactive)


def test_prefetcher_records_outcomes_in_addb(sage):
    ex = FeatureExtractor().attach(sage.store)
    pf = Prefetcher(sage.store, ex, sync=True, min_confidence=0.0).attach()
    _populate(sage, 3)
    for _ in range(3):
        for i in range(3):
            sage.get(f"o/{i}")
    ops = {r.op for r in sage.addb.records()}
    assert "prefetch_stage" in ops and "prefetch_hit" in ops
    assert pf.stats()["hits"] > 0


# ---------------------------------------------------------------------------
# ADDB windowed arrays (satellite)
# ---------------------------------------------------------------------------

def test_addb_window_and_to_arrays(sage):
    sage.create("w/1", block_size=256)
    sage.put("w/1", b"x" * 1024)
    sage.get("w/1")
    arrs = sage.addb.to_arrays(since_s=60.0)
    assert set(arrs) == {"ts", "op", "entity", "device", "nbytes",
                         "latency_s", "ok"}
    assert len(arrs["ts"]) == len(arrs["op"]) > 0
    assert arrs["ts"].dtype == np.float64 and arrs["ok"].all()
    gets = sage.addb.to_arrays(since_s=60.0, op="get")
    assert set(gets["op"]) <= {"get"} and (gets["entity"] == "w/1").all()
    assert sage.addb.window(0.0) == []  # empty window -> no records
    assert len(sage.addb.window(60.0)) == len(arrs["ts"])
