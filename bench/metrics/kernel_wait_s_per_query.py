"""Seconds the window's partitions spent blocked on the fused kernel and
its copy back to the host (the program's ``sage.kernel.wait`` spans) per
answered request; partition-seconds."""
from bench import program_spans


def reduce(view):
    ps = program_spans.load(view)
    return ps.per("sage.kernel.wait", len(view.get("requests", ()))) \
        if ps else None
