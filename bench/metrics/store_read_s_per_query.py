"""Seconds the window's partitions spent in store reads (the program's
``sage.store.read`` spans: ``Clovis.read_columns``, ``materialize``,
``get_array``) per answered request; partition-seconds, summed over
partitions that run at once."""
from bench import program_spans


def reduce(view):
    ps = program_spans.load(view)
    return ps.per("sage.store.read", len(view.get("requests", ()))) \
        if ps else None
