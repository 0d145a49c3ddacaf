"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy and idle time.

``reduce_trace`` reads one trace with ``jax.profiler.ProfileData`` and
returns, for the window that the benchmark's host span ``bench.window``
covers (or the whole trace where that span is missing):

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices that ran any;
- ``window_s``: the window's length;
- ``ops``: device seconds by operation, summed over devices; an
  operation is named by its program (the ``XLA Modules`` event it runs
  in) and its HLO name, without the instruction text that follows;
- ``gaps``: every idle interval of the first busy device, each labelled
  with the innermost ``bench.*`` host span that was open at its middle
  (``"none"`` where the host was in no such span).

Device planes are those named ``/device:<kind>:<n>``; on each, the line
named ``XLA Ops`` holds the operations (other lines, such as the module
and step lines, overlap it and are not counted).  Host spans are the
events named ``bench.*`` on any ``/host:`` plane.
"""
from __future__ import annotations

import bisect
import glob
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."

Interval = Tuple[int, int]


@dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    devices: int
    ops: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.ops.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.gaps,
                                          key=lambda kv: -kv[1])[:n]]


def find_xplane(trace_dir: Path) -> Optional[Path]:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    return Path(found[-1]) if found else None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals, sorted by start."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def complement(busy: Sequence[Interval], lo: int, hi: int
               ) -> List[Interval]:
    """Idle intervals of [lo, hi) around merged, clipped busy intervals."""
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def _op_name(name: str, start: int, modules, starts) -> str:
    """``<module>/<op>``: the op's HLO name (the text before `` = ``)
    inside the program whose module event holds its start (``modules``
    sorted by start, ``starts`` their starts)."""
    short = name.split(" = ", 1)[0]
    i = bisect.bisect_right(starts, start) - 1
    if i >= 0 and modules[i][1] <= start < modules[i][2]:
        return f"{modules[i][0]}/{short}"
    return short


def reduce_planes(planes) -> TraceSummary:
    """The reduction itself, over ``ProfileData.planes`` (or any objects
    with ``name``, ``lines[].name`` and ``lines[].events[]`` carrying
    ``name``, ``start_ns`` and ``duration_ns``)."""
    spans: List[Tuple[str, int, int]] = []
    device_ops: List[List[Tuple[str, int, int]]] = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(ev for ev in _events(line)
                             if ev[0].startswith(SPAN_PREFIX))
        elif DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(_events(line)) for line in plane.lines}
            mods = sorted(lines.get(MODULES_LINE, ()), key=lambda m: m[1])
            starts = [m[1] for m in mods]
            ops = [(_op_name(n, s, mods, starts), s, e)
                   for n, s, e in lines.get(OPS_LINE, ())]
            if ops:
                device_ops.append(ops)

    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        every = [ev for ops in device_ops for ev in ops] + spans
        if not every:
            return TraceSummary(0.0, 0.0, 0)
        lo, hi = min(s for _, s, _ in every), max(e for _, _, e in every)

    op_s: Dict[str, float] = {}
    busy_total = 0
    first_busy: Optional[List[Interval]] = None
    for ops in device_ops:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if min(e, hi) > max(s, lo)]
        for n, s, e in inside:
            op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9
        busy = union([(s, e) for _, s, e in inside])
        busy_total += sum(e - s for s, e in busy)
        if first_busy is None:
            first_busy = busy
    devices = len(device_ops)

    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    gaps = []
    for s, e in complement(first_busy or [], lo, hi):
        mid = (s + e) // 2
        open_at = [sp for sp in inner if sp[1] <= mid < sp[2]]
        label = (min(open_at, key=lambda sp: sp[2] - sp[1])[0]
                 if open_at else "none")
        gaps.append((label, (e - s) * 1e-9))
    return TraceSummary(busy_s=(busy_total / devices * 1e-9) if devices
                        else 0.0,
                        window_s=(hi - lo) * 1e-9, devices=devices,
                        ops=op_s, gaps=gaps)


def reduce_trace(path: Path) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes)


def describe(path: Path, limit: int = 6) -> List[str]:
    """Plane and line names with a few events each: the look at a trace
    that the reduction's assumptions rest on."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:limit]:
                out.append(f"    {ev.name[:80]!r} {ev.start_ns} "
                           f"{ev.duration_ns}")
    return out
