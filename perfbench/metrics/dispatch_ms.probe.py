"""Mean host milliseconds per batch in the executor's `launch` span (pad,
place, executable lookup and the asynchronous launch) in the window."""
import numpy as np


def read(run):
    d = [s.dur for s in run.window_spans("launch")]
    return float(np.mean(d)) * 1e3 if d else None
