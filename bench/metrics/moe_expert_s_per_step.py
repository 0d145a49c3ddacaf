"""Device seconds per training step of the expert layer: the device ops
of the compiled step whose HLO metadata carries the ``sage_moe`` scope
(names in ``moe_ops``, which ``drivers/train_nemotron3.py`` reads from the
compiled step), summed within the complete runs of the step's microbatch
loop (``step_loops``, the entry computation's ``while``) in the traced
window, over the number of those runs.  A run the trace cut short is not counted, nor are its
ops."""
import bisect

from bench import program_spans
from bench import trace as tr


def _short(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def seconds_per_step(planes, ops, loops):
    """Over ``ProfileData.planes`` (or objects shaped like them): the
    first device's seconds of ``ops`` inside runs of ``loops``, per run;
    None where the device ran no such loop."""
    ops = {n.lstrip("%") for n in ops}
    loops = {n.lstrip("%") for n in loops}
    for plane in planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        events = [(_short(n), s, e) for line in plane.lines
                  if line.name == tr.OPS_LINE for n, s, e in tr._events(line)]
        if not events:
            continue
        runs = sorted((s, e) for n, s, e in events if n in loops)
        if not runs:
            return None
        starts = [s for s, _ in runs]
        inside = 0
        for n, s, e in events:
            if n not in ops:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= runs[i][1]:
                inside += e - s
        return inside * 1e-9 / len(runs)
    return None


def reduce(view):
    if view["trace"].devices == 0 or not view.get("moe_ops") \
            or not view.get("step_loops"):
        return None
    path = tr.find_xplane(program_spans.TRACE_DIR)
    if path is None:
        return None
    from jax.profiler import ProfileData
    return seconds_per_step(ProfileData.from_file(str(path)).planes,
                            view["moe_ops"], view["step_loops"])
