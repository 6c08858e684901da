"""p50_ms: median latency, due time to answer, of every request due in
the window, the drained ones included (host clock)."""
import numpy as np


def read(run):
    lat = run.latencies_s()
    return float(np.percentile(lat, 50)) * 1e3 if lat.size else None
