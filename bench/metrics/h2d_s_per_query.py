"""Seconds the window's partitions spent padding the fused kernel's
inputs and handing them to the device (the program's
``sage.kernel.put`` spans) per answered request; partition-seconds."""
from bench import program_spans


def reduce(view):
    ps = program_spans.load(view)
    return ps.per("sage.kernel.put", len(view.get("requests", ()))) \
        if ps else None
