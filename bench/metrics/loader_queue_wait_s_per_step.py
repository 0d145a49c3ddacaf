"""Mean seconds a traced ``TokenLoader.__next__`` waited on its prefetch
queue (the program's ``sage.loader.wait`` spans: their seconds over
their count in the traced window)."""
from bench import program_spans


def reduce(view):
    ps = program_spans.load(view)
    return ps.per("sage.loader.wait", ps.counts.get("sage.loader.wait", 0)) \
        if ps else None
