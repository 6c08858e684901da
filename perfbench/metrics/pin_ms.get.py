"""Mean host milliseconds per batch in the executor's `pin` span (the
dispatch thread pinning the index generation for a taken batch, the
read of its sample key included) in the window."""
import numpy as np


def read(run):
    d = [s.dur for s in run.window_spans("pin")]
    return float(np.mean(d)) * 1e3 if d else None
