"""queue_ms.get: mean admission wait, submit to launch, of the requests
submitted in the window (the `request` spans' ``queue_us``)."""
import numpy as np


def read(run):
    q = [s.args["queue_us"] for s in run.window_spans("request") if s.args]
    return float(np.mean(q)) / 1e3 if q else None
