"""The program's own spans in a traced window.

The program annotates its stages as ``sage.<layer>.<stage>`` host spans
(``repro.core.addb.span``: ``jax.profiler.TraceAnnotation``s, on the
profiler's clock with the device ops).  ``load`` finds the window's
``.xplane.pb`` where ``bench/run.py`` writes it (``.bench_run/trace``
under the checkout), reads it once per run, and returns:

- ``seconds`` and ``counts`` by span name: every ``sage.*`` span on any
  thread, clipped to the window (the ``bench.window`` span, or where
  that is missing the extent of the device ops and ``bench.*`` spans,
  as ``bench.trace`` takes it); spans that run at once on several
  threads each count in full;
- ``gaps``: the first busy device's idle intervals in the window, each
  labelled with the innermost (shortest) ``sage.*`` span open at its
  middle on any thread, ``"none"`` where no such span was open: the
  rule of ``bench.trace``, over the program's spans.

It returns None where the trace has no device plane.  The per-layer
metrics ``store_read_s_per_query``, ``key_build_s_per_query``,
``h2d_s_per_query``, ``kernel_wait_s_per_query`` and
``loader_queue_wait_s_per_step`` read it.  To print the table of a
trace directory:

    python3 bench/program_spans.py <trace_dir> [--window <span>]
"""
from __future__ import annotations

import argparse
import functools
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))

from bench import trace as tr                              # noqa: E402

TRACE_DIR = ROOT / ".bench_run" / "trace"
PREFIX = "sage."


@dataclass
class ProgramSpans:
    window_s: float
    seconds: Dict[str, float] = field(default_factory=dict)
    counts: Dict[str, int] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def per(self, name: str, n: int) -> Optional[float]:
        """Seconds of ``name`` over ``n`` (requests or steps); None where
        the program has no such span or ``n`` is 0."""
        if not n or not self.counts.get(name):
            return None
        return self.seconds[name] / n

    def top_gaps(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.gaps,
                                          key=lambda kv: -kv[1])[:n]]

    def gap_table(self) -> Dict[str, Tuple[int, float]]:
        """(count, seconds) of the idle gaps by label."""
        out: Dict[str, List] = {}
        for label, s in self.gaps:
            c = out.setdefault(label, [0, 0.0])
            c[0] += 1
            c[1] += s
        return {k: (c, s) for k, (c, s) in sorted(out.items(),
                                                   key=lambda kv: -kv[1][1])}


def _label_gaps(gaps, spans) -> List[Tuple[str, float]]:
    """Each gap with the shortest span open at its middle: a sweep over
    spans sorted by start, keeping those still open (gaps come in time
    order, so a span that has ended stays behind)."""
    spans = sorted(spans, key=lambda sp: sp[1])
    active: List[Tuple[str, int, int]] = []
    i, out = 0, []
    for s, e in gaps:
        mid = (s + e) // 2
        while i < len(spans) and spans[i][1] <= mid:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[2] > mid]
        label = (min(active, key=lambda sp: sp[2] - sp[1])[0]
                 if active else "none")
        out.append((label, (e - s) * 1e-9))
    return out


def reduce_planes(planes, window_span: str = tr.WINDOW_SPAN
                  ) -> Optional[ProgramSpans]:
    """The reduction over ``ProfileData.planes`` (or objects shaped like
    them, as ``bench.trace.reduce_planes`` takes)."""
    sage: List[Tuple[str, int, int]] = []
    bench: List[Tuple[str, int, int]] = []
    all_ops: List[Tuple[int, int]] = []
    first_ops: Optional[List[Tuple[int, int]]] = None
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in tr._events(line):
                    if ev[0].startswith(PREFIX):
                        sage.append(ev)
                    elif ev[0].startswith(tr.SPAN_PREFIX):
                        bench.append(ev)
        elif tr.DEVICE_PLANE.match(plane.name):
            ops = [(s, e) for line in plane.lines if line.name == tr.OPS_LINE
                   for _, s, e in tr._events(line)]
            all_ops.extend(ops)
            if ops and first_ops is None:
                first_ops = ops
    if first_ops is None:
        return None

    windows = [(s, e) for n, s, e in bench if n == window_span]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        every = all_ops + [(s, e) for _, s, e in bench]
        lo, hi = min(s for s, _ in every), max(e for _, e in every)

    out = ProgramSpans(window_s=(hi - lo) * 1e-9)
    for name, s, e in sage:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            out.seconds[name] = out.seconds.get(name, 0.0) + d * 1e-9
            out.counts[name] = out.counts.get(name, 0) + 1
    busy = tr.union(tr.clip(first_ops, lo, hi))
    out.gaps = _label_gaps(tr.complement(busy, lo, hi), sage)
    return out


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, mtime_ns: int) -> Optional[ProgramSpans]:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def load(view) -> Optional[ProgramSpans]:
    """The program's spans of this run's traced window, or None where
    the trace has no device plane (or there is no trace)."""
    summary = view.get("trace")
    if summary is None or summary.devices == 0:
        return None
    path = tr.find_xplane(TRACE_DIR)
    if path is None:
        return None
    return _reduce_file(str(path), path.stat().st_mtime_ns)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--window", default=tr.WINDOW_SPAN,
                    help="the host span that bounds the window")
    args = ap.parse_args(argv)
    path = tr.find_xplane(Path(args.trace_dir))
    if path is None:
        print(f"no .xplane.pb under {args.trace_dir}", file=sys.stderr)
        return 1
    from jax.profiler import ProfileData
    ps = reduce_planes(ProfileData.from_file(str(path)).planes, args.window)
    if ps is None:
        print("the trace has no device plane", file=sys.stderr)
        return 1
    print(f"window {ps.window_s!r} s")
    print("span, count, seconds")
    for name in sorted(ps.seconds, key=lambda n: -ps.seconds[n]):
        print(f"{name}, {ps.counts[name]}, {ps.seconds[name]!r}")
    print("idle gaps by label: label, count, seconds")
    for label, (c, s) in ps.gap_table().items():
        print(f"{label}, {c}, {s!r}")
    print(f"longest idle gaps: {ps.top_gaps()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
