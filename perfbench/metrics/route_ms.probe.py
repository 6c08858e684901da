"""Mean host milliseconds per batch in the routed dispatcher's `route`
span (the argsort, bincount and reorder that split a batch into its
shards' sub-batches by the shard ids admission gave each key) in the
window.  A broadcast service has no `route` span and reports nothing."""
import numpy as np


def read(run):
    d = [s.dur for s in run.window_spans("route")]
    return float(np.mean(d)) * 1e3 if d else None
