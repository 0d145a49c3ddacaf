"""Smoke run of SAGE's main paths on a TPU, checked against plain references.

    python chip_smoke.py             # one chip: query, percipience, stream
                                     # and train phases
    python chip_smoke.py --chips 4   # four chips: one float32 train step on
                                     # a 2x2 data x model mesh vs one device

Every phase drives the system through the entry points a user calls, on
data made from ``--seed``, and compares what comes out with a plain
reference; a phase that fails fails the run.  Earlier lines of standard
output report each phase (seconds, kernel compiles, device memory peak);
the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero and prints no result.  It runs in
one process and keeps all of its state under ``.smoke_run/`` in the
checkout, removed at the end.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
WORK = ROOT / ".smoke_run"

# query phase: 32 colblock partitions x 1 Mi rows (~32 M rows, 512 MiB)
PARTITIONS, ROWS, GROUPS, ZIPF_S = 32, 1 << 20, 1000, 0.99
# percipience phase: objects x access-history steps
HEAT_OBJECTS, HEAT_HIST = 131072, 64
# stream phase: producers x tumbling 1 s windows x events per window
STREAM_PRODUCERS, STREAM_WINDOWS, STREAM_EVENTS, STREAM_KEYS = 4, 4, 256, 16
# train phase: mamba2-130m at its published widths
TRAIN_ARCH, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = "mamba2-130m", 8, 1024, 3

FLOAT_RTOL = 1e-3        # grouped float32 mean vs float64 numpy
HEAT_RTOL = 1e-4         # float32 heat scan vs float64 closed form
# 2x2-mesh step vs the one-device step, in float32 with full-precision
# matmuls, so the two layouts differ only in reduction order: loss and
# global gradient norm.  (In bf16 the rounding through 24 layers alone
# moves the gradient norm by about 2% between layouts on a v5e.)
# Parameters are reported, not compared: Adam's first step moves each by
# about +-lr whatever the gradient's size, so a near-zero gradient whose
# sign differs flips its update.
SHARDED_RTOL = 1e-4


def log(msg: str):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# store set-up (all under the run directory: nothing outside the checkout)
# ---------------------------------------------------------------------------

def make_clovis(root: Path):
    """A Clovis stack whose four tiers all live under ``root`` (the
    default puts T1 in /dev/shm, outside the checkout)."""
    from repro.core import Clovis
    from repro.core.addb import Addb
    from repro.core.tiers import TIER_ORDER, TierDevice, TierPool
    pools = {t: TierPool(t, [TierDevice(f"{t}/dev{i}", t,
                                        root / "tiers" / t / f"dev{i}")
                             for i in range(2)])
             for t in TIER_ORDER}
    return Clovis(root / "store", pools=pools, addb=Addb())


def shm_free_bytes() -> int:
    import os
    try:
        st = os.statvfs("/dev/shm")
        return st.f_bavail * st.f_frsize
    except OSError:
        return -1


# ---------------------------------------------------------------------------
# query phase: colblock table -> QueryService -> fused Pallas fold
# ---------------------------------------------------------------------------

def make_table(seed: int, partitions: int, rows: int, groups: int):
    """Columns (key, filter, value, fvalue) per partition: key zipf over
    ``groups`` (YCSB's 0.99 exponent), filter uniform [0, 100), value
    int32 [0, 1000), fvalue float32 [0, 1)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, groups + 1) ** ZIPF_S
    cdf = np.cumsum(p / p.sum())
    n = partitions * rows
    key = np.searchsorted(cdf, rng.random(n), side="right").astype(np.int32)
    np.minimum(key, groups - 1, out=key)
    filt = rng.integers(0, 100, n, dtype=np.int32)
    val = rng.integers(0, 1000, n, dtype=np.int32)
    fval = rng.random(n, dtype=np.float32)
    return key, filt, val, fval


def query_requests():
    """(tenant, name, op specs, reference) for the query mix: grouped
    sum / count / min / max over int32 values and one float mean."""
    import numpy as np
    from repro.analytics import col
    from repro.analytics.plan import Aggregate, Filter, KeyBy, op_to_spec

    def spec(*ops):
        return tuple(op_to_spec(o) for o in ops)

    def grouped(mask_fn, agg, vcol):
        def ref(t, groups):
            key, filt, val, fval = t
            m = mask_fn(filt)
            k = key[m]
            cnt = np.bincount(k, minlength=groups)
            live = np.nonzero(cnt)[0]
            if agg == "count":
                out = cnt
            elif agg == "sum":
                out = np.bincount(k, weights=val[m].astype(np.float64),
                                  minlength=groups)
                if out.max() >= 2 ** 31:
                    raise AssertionError("reference sum overflows int32")
                out = out.astype(np.int64)
            elif agg == "mean":
                out = np.bincount(k, weights=fval[m].astype(np.float64),
                                  minlength=groups) / np.maximum(cnt, 1)
            else:
                out = np.full(groups, 1000 if agg == "min" else -1, np.int64)
                (np.minimum if agg == "min" else np.maximum).at(
                    out, k, val[m])
            return live, out[live]
        return ref

    lt50 = lambda f: f < 50                           # noqa: E731
    return [
        ("tenant-a", "sum", spec(Filter(col(1) < 50), KeyBy(col(0)),
                                 Aggregate("sum", col(2))),
         grouped(lt50, "sum", 2)),
        ("tenant-b", "count", spec(Filter(col(1) >= 10), KeyBy(col(0)),
                                   Aggregate("count")),
         grouped(lambda f: f >= 10, "count", None)),
        ("tenant-a", "min", spec(Filter(col(1) < 90), KeyBy(col(0)),
                                 Aggregate("min", col(2))),
         grouped(lambda f: f < 90, "min", 2)),
        ("tenant-b", "max", spec(Filter(col(1) > 5), KeyBy(col(0)),
                                 Aggregate("max", col(2))),
         grouped(lambda f: f > 5, "max", 2)),
        ("tenant-a", "mean", spec(Filter(col(1) < 50), KeyBy(col(0)),
                                  Aggregate("mean", col(3))),
         grouped(lt50, "mean", 3)),
    ]


def query_phase(work: Path, *, seed: int, partitions: int = PARTITIONS,
                rows: int = ROWS, groups: int = GROUPS,
                kernel_mode: str = "pallas-tpu") -> dict:
    """Fails unless every query is right, the kernels ran as
    ``kernel_mode`` with at least one fused compile, and percipience
    swallowed no error."""
    import numpy as np
    from repro.analytics import kernels as K
    from repro.serving import QueryRequest, TenantConfig

    if K.kernel_mode() != kernel_mode:
        raise AssertionError(f"kernels would run as {K.kernel_mode()!r}, "
                             f"not {kernel_mode!r}")
    fused = {"pallas-tpu": K._fused_pallas_call,
             "xla-jit": K._fused_xla_call}[kernel_mode]

    t0 = time.perf_counter()
    table = make_table(seed, partitions, rows, groups)
    clovis = make_clovis(work / "query")
    clovis.enable_percipience()
    for i in range(partitions):
        sl = slice(i * rows, (i + 1) * rows)
        clovis.put_columnar(f"tbl/p{i:03d}", [c[sl] for c in table],
                            container="tbl", block_size=1 << 20)
    load_s = time.perf_counter() - t0

    fused0 = fused.cache_info().misses
    svc = clovis.serving([TenantConfig("tenant-a"), TenantConfig("tenant-b")],
                         workers=2)
    t1 = time.perf_counter()
    try:
        reqs = query_requests()
        subs = [svc.submit(QueryRequest(tenant, "tbl", ops, tag=name))
                for tenant, name, ops, _ in reqs]
        responses = [s.result(timeout=900) for s in subs]
    finally:
        svc.close()
    query_s = time.perf_counter() - t1

    checked = []
    for (tenant, name, _, ref), resp in zip(reqs, responses):
        if not resp.ok:
            raise AssertionError(f"query {name} ({tenant}) failed: "
                                 f"{resp.error}")
        keys, vals = resp.value
        want_keys, want = ref(table, groups)
        if not np.array_equal(np.asarray(keys), want_keys):
            raise AssertionError(f"query {name}: group keys differ")
        if name == "mean":
            np.testing.assert_allclose(np.asarray(vals, np.float64), want,
                                       rtol=FLOAT_RTOL,
                                       err_msg=f"query {name}")
        elif not np.array_equal(np.asarray(vals).astype(np.int64), want):
            raise AssertionError(f"query {name}: values differ from numpy")
        checked.append(name)
    fused_compiles = fused.cache_info().misses - fused0
    if fused_compiles == 0:
        raise AssertionError("no query compiled the fused kernel")
    if clovis.addb.advisory_error_count:
        raise AssertionError(f"{clovis.addb.advisory_error_count} "
                             f"percipience errors were swallowed")
    return {"rows": partitions * rows, "partitions": partitions,
            "groups_seen": int(len(responses[0].value[0])),
            "queries_ok": checked, "load_s": load_s, "query_s": query_s,
            "kernel_mode": kernel_mode, "fused_compiles": fused_compiles,
            "advisory_errors": 0}


# ---------------------------------------------------------------------------
# percipience phase: batched heat scan vs the closed form
# ---------------------------------------------------------------------------

def percipience_phase(*, seed: int, nobj: int = HEAT_OBJECTS,
                      hist: int = HEAT_HIST) -> dict:
    import numpy as np
    from repro.percipience.heat import heat_scores, heat_scores_ref
    rng = np.random.default_rng(seed + 1)
    now = 1.7e9
    ts = np.sort(now - rng.uniform(0, 900, (nobj, hist)), axis=1)
    mask = np.ones((nobj, hist))
    seen = rng.integers(0, hist + 1, nobj)       # right-aligned histories
    mask[np.arange(hist)[None, :] < (hist - seen)[:, None]] = 0.0
    ts *= mask
    t0 = time.perf_counter()
    got = heat_scores(ts, mask, now, half_life_s=120.0)
    heat_s = time.perf_counter() - t0
    want = heat_scores_ref(ts, mask, now, half_life_s=120.0)
    np.testing.assert_allclose(got, want, rtol=HEAT_RTOL, atol=1e-6)
    return {"objects": nobj, "hist": hist, "heat_s": heat_s,
            "max_rel_err": float(np.max(np.abs(got - want)
                                        / np.maximum(want, 1e-6)))}


# ---------------------------------------------------------------------------
# stream phase: EdgeIngestor -> ContinuousQuery vs batch recomputation
# ---------------------------------------------------------------------------

def stream_phase(work: Path, *, seed: int, producers: int = STREAM_PRODUCERS,
                 windows: int = STREAM_WINDOWS,
                 events: int = STREAM_EVENTS,
                 keys: int = STREAM_KEYS) -> dict:
    import numpy as np
    from repro.analytics import EventWindow, col
    from repro.core import StreamContext, StreamTap
    from repro.edge import (DeadLetterQueue, EdgeBuffer, EdgeIngestor,
                            IdempotencyLedger)

    rng = np.random.default_rng(seed + 2)
    clovis = make_clovis(work / "stream")
    eng = clovis.analytics()
    tap = StreamTap()
    ctx = StreamContext(n_producers=producers, attach=tap)
    cq = eng.run_continuous(
        eng.from_stream(ctx).key_by(col(0)).aggregate("sum", value=col(1)),
        EventWindow(size_s=1.0, allowed_lateness_s=0.5), delta_rows=64)
    ledger, dlq = IdempotencyLedger(), DeadLetterQueue()
    ings = [EdgeIngestor(ctx, EdgeBuffer(work / "edge" / f"p{p}",
                                         source=f"p{p}"),
                         producer=p, ledger=ledger, dlq=dlq,
                         addb=clovis.addb) for p in range(producers)]
    expected: dict = {}
    t0 = time.perf_counter()
    for w in range(windows):
        for i in range(events):
            ets = w + i / events
            for p, ing in enumerate(ings):
                k, v = int(rng.integers(0, keys)), int(rng.integers(0, 1000))
                ing.send(f"p{p}", np.array([k, v], np.int64), event_ts=ets)
                key = (f"p{p}", float(w), k)
                expected[key] = expected.get(key, 0) + v
    ctx.close()
    results = list(cq.drain()) + list(cq.close())
    stream_s = time.perf_counter() - t0

    streamed: dict = {}
    for r in results:
        ks, sums = r.value
        for k, s in zip(ks.tolist(), sums.tolist()):
            streamed[(r.stream_id, float(r.start), int(k))] = s
    if cq.late_count:
        raise AssertionError(f"{cq.late_count} in-order events were late")
    if streamed != expected:
        raise AssertionError("window sums differ from the generated events")
    bkeys, bvals = (eng.from_stream(tap).key_by(col(0))
                    .aggregate("sum", value=col(1)).collect())
    batch = dict(zip(np.asarray(bkeys).tolist(), np.asarray(bvals).tolist()))
    per_key: dict = {}
    for (_, _, k), s in streamed.items():
        per_key[k] = per_key.get(k, 0) + s
    if per_key != batch:
        raise AssertionError("window sums differ from batch recomputation")
    eng.close()
    return {"events": producers * windows * events, "windows": len(results),
            "stream_s": stream_s, "dead_letters": dlq.published}


# ---------------------------------------------------------------------------
# train phase: Trainer steps of mamba2-130m, checkpoint save + restore
# ---------------------------------------------------------------------------

def _run_config(arch: str, steps: int):
    from repro.configs.base import RunConfig
    return RunConfig(arch=arch, total_steps=steps, warmup_steps=1,
                     checkpoint_every=steps, remat="full", scan_layers=True,
                     seed=0)


def _bytes_equal(a, b) -> bool:
    import jax
    import numpy as np
    la, lb = jax.tree.leaves(jax.device_get(a)), jax.tree.leaves(
        jax.device_get(b))
    return len(la) == len(lb) and all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))


def train_phase(work: Path, *, cfg, batch: int = TRAIN_BATCH,
                seq: int = TRAIN_SEQ, steps: int = TRAIN_STEPS) -> dict:
    import math
    from repro.data.pipeline import TokenLoader, build_synthetic_corpus
    from repro.launch.train import Trainer

    clovis = make_clovis(work / "train")
    trainer = Trainer(cfg, _run_config(cfg.name, steps), work / "train",
                      clovis=clovis)
    build_synthetic_corpus(clovis, vocab=cfg.vocab_real, n_shards=4,
                           tokens_per_shard=batch * (seq + 1) * 2)
    loader = TokenLoader(clovis, batch=batch, seq=seq)
    try:
        t0 = time.perf_counter()
        params, opt, hist = trainer.train(steps, loader, log_every=1)
        train_s = time.perf_counter() - t0
    finally:
        loader.close()
    losses = [loss for _, loss in hist]
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"train losses not finite: {losses}")
    t1 = time.perf_counter()
    got = trainer.try_restore()
    restore_s = time.perf_counter() - t1
    trainer.ckpt.close()
    if got is None or got[0] != steps:
        raise AssertionError(f"no checkpoint at step {steps} to restore")
    _, rparams, ropt = got
    if not (_bytes_equal(params, rparams) and _bytes_equal(opt, ropt)):
        raise AssertionError("restored checkpoint is not byte-identical")
    return {"arch": cfg.name, "steps": steps, "batch": batch, "seq": seq,
            "losses": losses, "train_s": train_s, "restore_s": restore_s,
            "restore_identical": True}


# ---------------------------------------------------------------------------
# four chips: one sharded train step vs the same step on one device
# ---------------------------------------------------------------------------

def sharded_phase(work: Path, *, cfg, batch: int = TRAIN_BATCH,
                  seq: int = TRAIN_SEQ, data: int = 2, model: int = 2
                  ) -> dict:
    import jax
    import numpy as np
    from repro.launch.mesh import mesh_context
    from repro.launch.train import Trainer
    from repro.models import model as mdl
    from repro.models.common import axis_rules

    cfg = cfg.scaled(dtype="float32")
    tokens = mdl.make_batch(jax.random.key(1), cfg, batch, seq)
    host_batch = {k: np.asarray(v) for k, v in tokens.items()}
    clovis = make_clovis(work / "sharded")
    out = {}
    for name, (d, m) in (("one_device", (1, 1)), ("mesh", (data, model))):
        trainer = Trainer(cfg, _run_config(cfg.name, 1), work / "sharded",
                          clovis=clovis, data_mesh=d, model_mesh=m)
        params, opt = trainer.init_state(seed=0)
        t0 = time.perf_counter()
        with mesh_context(trainer.mesh), axis_rules(trainer.rules), \
                jax.default_matmul_precision("float32"):
            params, opt, metrics = trainer.train_step(
                params, opt, trainer.place_batch(host_batch))
            loss = float(metrics["loss"])
        out[name] = {"loss": loss,
                     "grad_norm": float(metrics["grad_norm"]),
                     "params": jax.device_get(params),
                     "devices": len(trainer.mesh.devices.flat),
                     "step_s": time.perf_counter() - t0}
        trainer.ckpt.close()
    a, b = out["one_device"], out["mesh"]
    np.testing.assert_allclose(b["loss"], a["loss"], rtol=SHARDED_RTOL)
    np.testing.assert_allclose(b["grad_norm"], a["grad_norm"],
                               rtol=SHARDED_RTOL)
    la, lb = jax.tree.leaves(a.pop("params")), jax.tree.leaves(b.pop("params"))
    worst = max(float(np.max(np.abs(np.asarray(x, np.float32)
                                    - np.asarray(y, np.float32))))
                for x, y in zip(la, lb))
    return {"arch": cfg.name, "dtype": cfg.dtype, "batch": batch,
            "seq": seq, "mesh": f"{data}x{model}", "one_device": a,
            "sharded": b,
            "loss_rel_diff": abs(b["loss"] - a["loss"]) / abs(a["loss"]),
            "param_max_abs_diff": worst}


# ---------------------------------------------------------------------------

def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _run(name: str, fn, dev) -> bool:
    """Run one phase; print its report line (or its traceback)."""
    from repro.analytics import kernels as K
    t0 = time.perf_counter()
    try:
        res = fn()
    except Exception:
        traceback.print_exc()
        log(f"phase {name}: FAILED after {time.perf_counter() - t0:.3f}s")
        return False
    res.update(seconds=time.perf_counter() - t0,
               kernel_compiles=K.kernel_cache_info()["misses"],
               device_peak_bytes=_peak_bytes(dev))
    log(f"phase {name}: ok " + json.dumps(res, sort_keys=True))
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every single-chip phase; 4: only the sharded "
                         "train step on a 2x2 mesh and its one-device "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    from repro.analytics import kernels as K
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"jax {jax.__version__}; devices: {len(devices)} x "
        f"{devices[0].device_kind}; /dev/shm free bytes: {shm_free_bytes()}")

    dev = devices[0]
    cfg = get_config(TRAIN_ARCH)
    shutil.rmtree(WORK, ignore_errors=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            ok = _run("sharded_train", lambda: sharded_phase(WORK, cfg=cfg),
                      dev)
        else:
            # every phase runs, so one run reports every failure
            ok = all([
                _run("query", lambda: query_phase(WORK, seed=args.seed),
                     dev),
                _run("percipience",
                     lambda: percipience_phase(seed=args.seed), dev),
                _run("stream", lambda: stream_phase(WORK, seed=args.seed),
                     dev),
                _run("train", lambda: train_phase(WORK, cfg=cfg), dev),
            ])
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    log(f"total: {time.perf_counter() - t0:.3f}s; kernel compiles "
        f"{K.kernel_cache_info()}; device peak bytes {_peak_bytes(dev)}")
    print(json.dumps({"ok": ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
