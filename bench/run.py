"""SAGE's on-chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell's traffic file
``bench/traffic/<cell>.json`` names its configuration
(``bench/configs/<config>.json``) and its driver
(``bench/drivers/<driver>.py``); the metrics it reports are the entries
of ``BENCHMARK.json`` that apply to it, and each per-layer metric is
computed by ``bench/metrics/<metric>.py``.  A new cell, configuration or
metric is a new file, with no edit to this one.

A run builds the system from ``--seed`` (set-up, timed as ``setup_s``
from process start), measures ``--seconds`` of the cell's traffic,
then checks what the timed path produced against a plain reference.
With ``--trace 1`` the window is traced by the JAX profiler and the
metrics are the per-layer ones.  The last line of standard output is
one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, ``breakdown`` when traced, and ``checks``: each compared
number beside its limit, which also end standard error).  Without a
TPU, or with fewer chips than the cell needs, the run exits non-zero
and prints no result.  All state lives in ``.bench_run/`` in the
checkout, removed at exit; JAX's compile cache in ``.jax_cache/`` there
unless ``JAX_COMPILATION_CACHE_DIR`` is set.
"""
from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse                                            # noqa: E402
import contextlib                                          # noqa: E402
import importlib.util                                      # noqa: E402
import json                                                # noqa: E402
import shutil                                              # noqa: E402
import sys                                                 # noqa: E402
import threading                                           # noqa: E402
import traceback                                           # noqa: E402
from dataclasses import dataclass, field                   # noqa: E402
from pathlib import Path                                   # noqa: E402
from typing import Any, Dict, List, Optional               # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
SRC = ROOT / "src"
WORK = ROOT / ".bench_run"


def log(msg: str):
    print(msg, flush=True)


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a driver or reducer by file path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_int(seed: int) -> int:
    """Any whole number as a non-negative numpy seed."""
    return int(seed) % (1 << 64)


@dataclass
class Context:
    """What a driver gets: its cell, configuration and seed, a work
    directory, the host spans, and the window and memory hooks whose
    order the contract fixes (trace the window only; read the memory
    peak before the reference runs)."""
    name: str
    traffic: Dict
    config: Dict
    seed: int
    seconds: float
    trace: bool
    work: Path
    spans: Any
    window_start: Optional[float] = None     # time.time()
    window_s: Optional[float] = None
    peak_bytes: int = -1
    trace_dir: Optional[Path] = None
    notes: List[str] = field(default_factory=list)
    # programs JAX built by phase: compiled, or read from the persistent
    # cache (both pass the backend-compile event; hits are also counted)
    compiles: Dict[str, int] = field(
        default_factory=lambda: {"setup": 0, "window": 0, "after": 0})
    cache_hits: Dict[str, int] = field(
        default_factory=lambda: {"setup": 0, "window": 0, "after": 0})
    phase: str = "setup"

    def on_duration(self, event: str, duration_s: float, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles[self.phase] += 1

    def on_event(self, event: str, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits[self.phase] += 1

    def log(self, msg: str):
        log(msg)

    @contextlib.contextmanager
    def window(self):
        """Wrap the measured window: starts the profiler first in traced
        runs, so its start-up is set-up, and stops it after the cell's
        ``trace_seconds`` where its traffic file sets them (the device
        tracer keeps a bounded number of events, which a cell of many
        small operations fills before a window ends); the driver sets
        ``window_s``."""
        import jax
        stop = None
        if self.trace:
            self.trace_dir = self.work / "trace"
            # host spans and device ops; no Python function tracing,
            # which would slow the host's part of the window
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            stop = TraceStop(self.traffic.get("trace_seconds"))
        self.window_start = time.time()
        self.phase = "window"
        try:
            with self.spans.span("bench.window"):
                yield
        finally:
            self.phase = "after"
            if stop is not None:
                stop()

    def read_peak(self):
        import jax
        peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
                 for d in jax.local_devices()]
        self.peak_bytes = max(peaks)


class TraceStop:
    """Stops the profiler once: when called, or from a timer after
    ``seconds`` where they are given.  A call waits for a stop that the
    timer has begun, so the trace is written when it returns."""

    def __init__(self, seconds: Optional[float]):
        self._lock = threading.Lock()
        self._done = False
        self._timer = (threading.Timer(float(seconds), self)
                       if seconds else None)
        if self._timer is not None:
            self._timer.daemon = True
            self._timer.start()

    def __call__(self):
        import jax
        if self._timer is not None:
            self._timer.cancel()
        with self._lock:
            if not self._done:
                self._done = True
                jax.profiler.stop_trace()


def applies(metric: Dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def load_cell(name: str, bench_dir: Path = BENCH):
    """(traffic, configuration, driver module) of a cell, by its name."""
    traffic = load_json(bench_dir / "traffic" / f"{name}.json")
    config = load_json(bench_dir / "configs" / f"{traffic['config']}.json")
    driver = load_module(bench_dir / "drivers" / f"{traffic['driver']}.py",
                         f"bench_driver_{traffic['driver']}")
    return traffic, config, driver


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             spec: Dict, bench_dir: Path = BENCH, work: Path = WORK) -> Dict:
    """Run one cell; returns the result object (without printing it)."""
    from bench.spans import Spans
    traffic, config, driver = load_cell(name, bench_dir)
    ctx = Context(name, traffic, config, seed_int(seed), float(seconds),
                  bool(trace), work, Spans())
    import jax
    jax.monitoring.register_event_duration_secs_listener(ctx.on_duration)
    jax.monitoring.register_event_listener(ctx.on_event)
    try:
        out = driver.run(ctx)
    finally:
        jax.monitoring.unregister_event_duration_listener(ctx.on_duration)
        jax.monitoring.unregister_event_listener(ctx.on_event)
    log("programs built (compiled or read from the persistent cache): "
        f"{ctx.compiles}; of them read from the cache: {ctx.cache_hits}")

    e2e = [m for m in spec["end_to_end"] if applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    values = dict(out["metrics"])
    values["setup_s"] = ctx.window_start - PROCESS_START
    metrics: Dict[str, Dict] = {}
    result: Dict[str, Any] = {}
    if trace:
        from bench import trace as tr
        summary = tr.reduce_trace(tr.find_xplane(ctx.trace_dir))
        view = dict(out.get("layer", {}), trace=summary,
                    window_s=ctx.window_s,
                    device_kind=jax.devices()[0].device_kind)
        for m in spec["per_layer"]:
            if not applies(m, name, reported):
                continue
            reducer = load_module(bench_dir / "metrics" / f"{m['name']}.py",
                                  "bench_metric_" + m["name"].replace(".", "_"))
            v = reducer.reduce(view)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result["breakdown"] = {"device_ops": summary.top_ops(),
                               "idle_gaps": summary.top_gaps()}
        device_extra = {"busy_s": summary.busy_s,
                        "window_s": summary.window_s}
    else:
        for m in e2e:
            if m["name"] not in values:
                raise KeyError(f"driver reported no {m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
        device_extra = {}

    devs = jax.devices()
    checks = {k: {"value": float(v), "limit": float(lim)}
              for k, (v, lim) in out["checks"].items()}
    correct = bool(out["correct"]) and all(
        c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": dict({"platform": devs[0].platform,
                              "kind": devs[0].device_kind,
                              "count": len(devs),
                              "memory_peak_bytes": ctx.peak_bytes},
                             **device_extra),
              **result, "checks": checks}
    for line in ctx.notes:
        log(line)
    log(f"spans: {json.dumps(ctx.spans.summary())}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"bench: no repro package under {SRC}", file=sys.stderr)
        return 2
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        print(f"bench: no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    chips = int(cells[args.workload]["chips"])
    sys.path[:0] = [str(ROOT), str(SRC)]
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    # every program, the small kernels too, so later runs compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"compile cache: {enable_compile_cache()}")
    log(f"jax {jax.__version__}; {len(devices)} x {devices[0].device_kind}")

    shutil.rmtree(WORK, ignore_errors=True)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), spec=spec)
    except Exception:
        traceback.print_exc()
        return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
