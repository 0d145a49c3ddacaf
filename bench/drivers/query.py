"""Query driver: closed-loop query streams through ``QueryService.submit``.

Set-up generates the configuration's table from the seed, loads it
through ``Clovis.put_columnar`` (one colblock object per
``partition_rows`` rows) into a four-tier store under the run
directory, starts the front door with the configuration's deployment
settings, draws each stream's parameters from the seed, and warms every
request the window will send by sending each once.

In the window each stream is one closed-loop client with its own
tenant: it sends the query's aggregates as concurrent requests, waits
for all of them, and sends again, until ``--seconds`` have passed; the
requests in flight then complete, and the window ends with the last of
them.  Each request's latency is taken from its submission to its
response on the client's side.  A query is answered when all its
requests are: it covers the table once, and its latency runs from its
first submission to its last response.

Afterwards every answer of the window is compared with the numpy
reference of ``bench.ref.query`` over the same generated columns.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Dict, List

import numpy as np

from bench.ref import expr as bx
from bench.ref.query import compare, grouped

RESULT_TIMEOUT_S = 300.0


def make_clovis(root: Path, devices_per_tier: int):
    """A Clovis stack whose four tiers all live under ``root`` (the
    default puts T1 in /dev/shm, outside the checkout)."""
    from repro.core import Clovis
    from repro.core.addb import Addb
    from repro.core.tiers import TIER_ORDER, TierDevice, TierPool
    pools = {t: TierPool(t, [TierDevice(f"{t}/dev{i}", t,
                                        root / "tiers" / t / f"dev{i}")
                             for i in range(devices_per_tier)])
             for t in TIER_ORDER}
    return Clovis(root / "store", pools=pools, addb=Addb())


def generate(config: Dict, seed: int) -> Dict[str, np.ndarray]:
    import importlib
    gen = importlib.import_module(f"bench.gen.{config['generator']}")
    return gen.generate(config, seed)


def draw_params(spec: Dict, streams: int, seed: int) -> List[Dict]:
    """Each stream's parameters, drawn in name order: each is
    ``{"uniform_int": [lo, hi]}``, a whole number, and
    ``"distinct": true`` draws it without repeats across streams."""
    rng = np.random.default_rng([seed, 1])
    out: List[Dict] = [{} for _ in range(streams)]
    for name, p in sorted(spec.items()):
        lo, hi = p["uniform_int"]
        vals = rng.choice(np.arange(lo, hi + 1), size=streams,
                          replace=not p.get("distinct", False))
        for s in range(streams):
            out[s][name] = int(vals[s])
    return out


def resolve_query(query: Dict, params: Dict) -> Dict:
    """A traffic file's query with one stream's parameters filled in."""
    out = {k: bx.resolve(v, params) for k, v in query.items()
           if k != "aggregates"}
    out["aggregates"] = [dict(a, value=bx.resolve(a["value"], params))
                         if "value" in a else a for a in query["aggregates"]]
    return out


def to_program(e, index: Dict[str, int]):
    """A resolved bench expression as the program's ``Expr``."""
    from repro.analytics import col
    if isinstance(e, str):
        return col(index[e])
    if isinstance(e, list):
        return bx.OPS[e[0]](to_program(e[1], index), to_program(e[2], index))
    return e


def build_requests(query: Dict, tenant: str, table: str,
                   index: Dict[str, int], tag: str):
    """One QueryRequest per aggregate of a resolved query."""
    from repro.analytics.plan import Aggregate, Filter, KeyBy, op_to_spec
    from repro.serving import QueryRequest
    pre = []
    if query.get("filter") is not None:
        pre.append(Filter(to_program(query["filter"], index)))
    pre.append(KeyBy(to_program(query["group"], index)))
    reqs = []
    for agg in query["aggregates"]:
        value = (None if agg["agg"] == "count"
                 else to_program(agg["value"], index))
        if value is not None and not hasattr(value, "to_spec"):
            raise ValueError(f"aggregate {agg['name']} reads no column")
        ops = tuple(op_to_spec(o) for o in pre + [Aggregate(agg["agg"],
                                                            value)])
        reqs.append((agg, QueryRequest(tenant, table, ops,
                                       tag=f"{tag}/{agg['name']}")))
    return reqs


def logical_cost(query: Dict, rows: int):
    """Bytes and operations a query's work needs at the least, whatever
    runs it: every column it references read once, one int32 group id
    a row; the filter, group key and value expressions and one fold per
    aggregate, a row."""
    exprs = [query.get("filter"), query["group"]] + [
        a.get("value") for a in query["aggregates"]]
    exprs = [e for e in exprs if e is not None]
    cols = set().union(*(bx.columns(e) for e in exprs))
    ops = sum(bx.count_ops(e) for e in exprs) + len(query["aggregates"])
    return rows * 4 * (len(cols) + 1), rows * ops


def run(ctx) -> Dict:
    cfg, traffic = ctx.config, ctx.traffic
    dep = cfg["deployment"]
    names = [c["name"] for c in cfg["columns"]]
    index = {n: i for i, n in enumerate(names)}
    table = cfg["table"]

    with ctx.spans.span("bench.generate"):
        cols = generate(cfg, ctx.seed)
    n_rows = int(len(cols[names[0]]))
    part = int(cfg["partition_rows"])
    clovis = make_clovis(ctx.work / "store", int(dep["devices_per_tier"]))
    if dep.get("percipience"):
        clovis.enable_percipience()
    with ctx.spans.span("bench.load"):
        for i, start in enumerate(range(0, n_rows, part)):
            clovis.put_columnar(f"{table}/p{i:04d}",
                                [cols[n][start:start + part] for n in names],
                                container=table,
                                block_size=int(dep["block_bytes"]))
    n_parts = -(-n_rows // part)

    from repro.analytics import kernels as K
    from repro.serving import TenantConfig
    streams = int(traffic["streams"])
    tenants = [f"tenant-{s}" for s in range(streams)]
    svc = clovis.serving([TenantConfig(t) for t in tenants],
                         workers=int(dep["workers"]),
                         partial_cache_size=int(dep["partial_cache_size"]),
                         plan_cache_size=int(dep["plan_cache_size"]))
    params = draw_params(traffic.get("params", {}), streams, ctx.seed)
    queries = [resolve_query(traffic["query"], p) for p in params]
    ctx.log(f"table: {n_rows} rows in {n_parts} partitions; streams "
            f"{params}")

    def wait(sub):
        resp = sub.result(timeout=RESULT_TIMEOUT_S)
        return resp, time.perf_counter()

    try:
        with ctx.spans.span("bench.warmup"):
            for s in range(streams):
                subs = [svc.submit(req) for _, req in build_requests(
                    queries[s], tenants[s], table, index,
                    f"bench/warm/{s}")]
                for sub in subs:
                    if not wait(sub)[0].ok:
                        raise RuntimeError(f"warm-up request of stream {s} "
                                           f"failed")
        compiles0 = K.kernel_cache_info()["misses"]
        records: List[Dict] = []
        lock = threading.Lock()
        late: List[float] = []
        pools = [ThreadPoolExecutor(len(traffic["query"]["aggregates"]),
                                    thread_name_prefix=f"bench-wait{s}")
                 for s in range(streams)]

        def record(s, it, agg, req, t0, t1, resp, error):
            with lock:
                records.append({"query": (s, it), "stream": s, "agg": agg,
                                "tag": req.tag, "t0": t0, "t1": t1,
                                "resp": resp, "error": error})

        def client(s: int, stop_at: float):
            it, last_done = 0, None
            while time.perf_counter() < stop_at:
                if last_done is not None:
                    late.append(time.perf_counter() - last_done)
                with ctx.spans.span("bench.submit"):
                    sent = []
                    for agg, req in build_requests(
                            queries[s], tenants[s], table, index,
                            f"bench/{s}/{it}"):
                        t = time.perf_counter()
                        try:
                            sent.append((agg, req, t, pools[s].submit(
                                wait, svc.submit(req))))
                        except Exception as e:         # shed at the door
                            record(s, it, agg, req, t, None, None,
                                   repr(e))
                with ctx.spans.span("bench.wait"):
                    last_done = time.perf_counter()
                    for agg, req, t, f in sent:
                        try:
                            resp, t1 = f.result()
                            err = None if resp.ok else resp.error
                            last_done = max(last_done, t1)
                        except Exception as e:         # never answered
                            resp, t1, err = None, None, repr(e)
                        record(s, it, agg, req, t, t1, resp, err)
                it += 1

        with ctx.window():
            t_start = time.perf_counter()
            stop_at = t_start + ctx.seconds
            threads = [threading.Thread(target=client, args=(s, stop_at),
                                        name=f"bench-client{s}")
                       for s in range(streams)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            t1s = [r["t1"] for r in records if r["t1"] is not None]
            t_end = max(t1s) if t1s else time.perf_counter()
        ctx.window_s = t_end - t_start
        for p in pools:
            p.shutdown(wait=True)
        ctx.read_peak()
        compiles = K.kernel_cache_info()["misses"] - compiles0
        serving = clovis.addb.serving_trace()
    finally:
        svc.close()

    # a query is answered when every one of its requests is
    done = [r for r in records if r["error"] is None]
    by_query: Dict = {}
    for r in records:
        by_query.setdefault(r["query"], []).append(r)
    n_aggs = len(traffic["query"]["aggregates"])
    answered = {k: v for k, v in by_query.items()
                if len(v) == n_aggs and all(r["error"] is None for r in v)}
    lat = {k: max(r["t1"] for r in v) - min(r["t0"] for r in v)
           for k, v in answered.items()}
    ctx.notes.append(f"queries: {len(by_query)} sent, {len(answered)} "
                     f"answered; requests: {len(records)} sent, "
                     f"{len(done)} answered right-shaped; kernel compiles "
                     f"in the window {compiles}; client gap between "
                     f"queries max {max(late) if late else 0.0!r} s; "
                     f"device peak bytes {ctx.peak_bytes}; percipience "
                     f"errors swallowed {clovis.addb.advisory_error_count}")

    # the reference, once the window is closed and the peak read
    checks = {"groups": 0.0, "count": 0.0, "rel": 0.0}
    refs = [grouped(cols, q) for q in queries]
    for r in done:
        keys, vals = r["resp"].value
        d = compare((keys, vals), refs[r["stream"]][r["agg"]["name"]],
                    r["agg"]["agg"])
        for k, v in d.items():
            checks[k] = max(checks[k], v)
    limits = traffic["limits"]
    groups_seen = {len(refs[s][a["name"]][0]) for s in range(streams)
                   for a in queries[s]["aggregates"]}
    ctx.notes.append(f"reference groups per query: {sorted(groups_seen)}")

    wait_by_tag: Dict[str, float] = {}
    for rec in serving:
        if rec["stage"] in ("admit", "queue"):
            wait_by_tag[rec["query"]] = (wait_by_tag.get(rec["query"], 0.0)
                                         + rec["latency_s"])
    layer_reqs = [{"plan_s": r["resp"].stats.plan_s,
                   "exec_s": r["resp"].stats.exec_s,
                   "bytes_scanned": r["resp"].stats.bytes_scanned,
                   "rows": n_rows,
                   "front_door_s": wait_by_tag.get(r["resp"].tag)}
                  for r in done]
    layer_queries = [{"latency_s": v} for v in lat.values()]
    if ctx.trace:
        import jax
        from bench import peaks
        peak = peaks.load(jax.devices()[0].device_kind)
        for q, (s, _) in zip(layer_queries, lat):
            q["least_s"] = peaks.least_seconds(
                *logical_cost(queries[s], n_rows), peak)
    return {
        "attempted": len(by_query),
        "failed": len(by_query) - len(answered),
        "correct": len(answered) == len(by_query) and len(answered) > 0,
        "metrics": {"scan_rows_per_s": len(answered) * n_rows
                    / ctx.window_s},
        "checks": {k: (checks[k], limits[k]) for k in limits},
        "layer": {"requests": layer_reqs, "queries": layer_queries},
    }


def control(ctx) -> Dict:
    """The lower-precision control's readings at the cell's size: the
    reference computed in bfloat16 put in the program's place, compared
    with the float64 reference on the same table and parameters."""
    cfg, traffic = ctx.config, ctx.traffic
    cols = generate(cfg, ctx.seed)
    streams = int(traffic["streams"])
    params = draw_params(traffic.get("params", {}), streams, ctx.seed)
    worst = {"groups": 0.0, "count": 0.0, "rel": 0.0}
    for p in params:
        q = resolve_query(traffic["query"], p)
        want, got = grouped(cols, q), grouped(cols, q, precision="bfloat16")
        for a in q["aggregates"]:
            for k, v in compare(got[a["name"]], want[a["name"]],
                                a["agg"]).items():
                worst[k] = max(worst[k], v)
    return worst
