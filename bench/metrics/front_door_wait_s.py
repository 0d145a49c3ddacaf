"""Mean seconds a window request spent at the front door before it ran:
its ``admit`` plus ``queue`` stages in the ADDB serving trace."""


def reduce(view):
    waits = [r["front_door_s"] for r in view.get("requests", ())
             if r.get("front_door_s") is not None]
    return sum(waits) / len(waits) if waits else None
