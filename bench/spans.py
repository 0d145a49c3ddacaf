"""Host spans the benchmark puts around the calls it makes into the
program: kept in memory with their thread and host-clock times, and
written into the profiler's trace as ``jax.profiler.TraceAnnotation``s
so that ``bench.trace`` can label the device's idle gaps with them."""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Tuple


class Spans:
    def __init__(self):
        self.records: List[Tuple[str, int, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.records.append((name, threading.get_ident(), t0,
                                 time.perf_counter()))

    def total(self, name: str) -> Tuple[int, float]:
        """(count, summed seconds) of the spans called ``name``."""
        d = [t1 - t0 for n, _, t0, t1 in self.records if n == name]
        return len(d), float(sum(d))

    def summary(self) -> Dict[str, Tuple[int, float]]:
        return {n: self.total(n) for n in sorted({r[0] for r in
                                                  self.records})}
