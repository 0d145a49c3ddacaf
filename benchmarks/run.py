"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only stream|dht|checkpoint|
                                             streams|clovis|percipience|
                                             analytics|streaming|cluster|
                                             edge|serving|compaction|
                                             kernels]
                                            [--quick] [--smoke]
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, metavar="SUITE",
                    help="run a single benchmark suite (validated against "
                         "the live suite table, so the help text can never "
                         "drift from what actually runs)")
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes for CI-speed runs")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + no perf assertions (CI bench-smoke "
                         "job: proves the harness runs and emits JSON)")
    args = ap.parse_args()

    from benchmarks import (bench_analytics, bench_checkpoint, bench_clovis,
                            bench_cluster, bench_compaction, bench_dht,
                            bench_edge, bench_kernels, bench_percipience,
                            bench_serving, bench_stream_windows,
                            bench_streams)

    suites = {
        # paper Fig. 3: STREAM bandwidth, memory vs storage windows
        "stream": lambda: bench_stream_windows.run(
            n_elems=500_000 if args.quick else 2_000_000),
        # paper Fig. 4: DHT random access overhead per tier
        "dht": lambda: bench_dht.run(
            n_elems=20_000 if args.quick else 50_000),
        # paper Fig. 5: HACC-IO checkpoint/restart strategies
        "checkpoint": lambda: bench_checkpoint.run(
            sizes=((4, 32768), (8, 65536)) if args.quick
            else ((8, 65536), (16, 131072), (32, 131072))),
        # paper Fig. 7: stream offload scaling
        "streams": lambda: bench_streams.run(
            producer_counts=(4, 16) if args.quick else (4, 16, 64)),
        # §3.2: Clovis op + function-shipping microbenches
        "clovis": bench_clovis.run,
        # percipience loop: prefetch hit-rate / latency vs reactive HSM
        "percipience": lambda: bench_percipience.run(
            n_reads=200 if args.quick else 400),
        # analytics pushdown: bytes-moved / modelled latency vs fetch-all
        "analytics": lambda: bench_analytics.run(
            n_objects=8 if args.quick else 16,
            rows=4096 if args.quick else 8192,
            stream_elements=500 if args.quick else 2000),
        # continuous queries: incremental watermarked windows vs
        # drain-then-batch over the same live stream
        "streaming": lambda: bench_stream_windows.run_streaming(
            n_elements=800 if args.quick else 2000),
        # scale-out cluster: query throughput at 1/4/16 nodes +
        # kill-a-node-mid-scan byte-identical failover check
        "cluster": lambda: bench_cluster.run(
            partitions=96 if args.quick else 128,
            rows=512 if args.quick else 2048,
            repeats=2 if args.quick else 3),
        # resilient edge ingestion: seeded chaos gauntlet (duplicates,
        # reorders, poison, producer crash+replay, torn tails) with the
        # exactly-once byte-identity assertion
        "edge": lambda: bench_edge.run(
            n_events=400 if args.quick else 1200,
            producers=2 if args.quick else 4),
        # log-structured compaction: ingest-while-query throughput +
        # read amplification with/without the compactor, plus snapshot
        # byte-identity probes under live churn
        "compaction": lambda: bench_compaction.run(
            duration_s=2.0 if args.quick else 4.0,
            strict=not args.quick),
        # serving front door: multi-tenant zipfian load at 10/100/1000
        # sessions — tail latency, Jain fairness, shed + dedup rates
        "serving": lambda: bench_serving.run(
            levels=(10, 50) if args.quick else (10, 100, 1000),
            partitions=8 if args.quick else 16,
            rows=512 if args.quick else 1024,
            strict=not args.quick),
        # fused filter->aggregate kernel vs unfused mask-then-reduce:
        # compiled (non-interpret) timings, byte-identity, closure-cache
        # reuse — writes results/BENCH_kernels.json
        "kernels": lambda: bench_kernels.run(
            rows=1 << 18 if args.quick else 1 << 20,
            smoke=args.smoke),
    }
    if args.only is not None and args.only not in suites:
        ap.error(f"unknown benchmark {args.only!r} for --only; known "
                 f"benchmarks: {', '.join(sorted(suites))}")
    chosen = [args.only] if args.only else list(suites)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for name in chosen:
        print(f"# --- {name} ---")
        try:
            suites[name]()
        except Exception:
            failures += 1
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
