"""Seconds the window's partitions spent evaluating group keys and
building segment ids (the program's ``sage.exec.keys`` spans) per
answered request; partition-seconds."""
from bench import program_spans


def reduce(view):
    ps = program_spans.load(view)
    return ps.per("sage.exec.keys", len(view.get("requests", ()))) \
        if ps else None
