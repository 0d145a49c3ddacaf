"""95th percentile of the window's answered queries' latencies: from a
query's first submission to its last response, on the client's side."""
import numpy as np


def reduce(view):
    lat = [q["latency_s"] for q in view.get("queries", ())]
    return float(np.percentile(lat, 95)) if lat else None
