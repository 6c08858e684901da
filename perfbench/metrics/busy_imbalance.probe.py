"""busy_imbalance.probe: the busiest chip's device-busy time over the
mean chip's, in percent (100: every chip equally busy), from the traced
window's union of operation intervals on each chip, as
`tracefile.busy_ns` takes it.  Every chip the cell asks for counts: one
with no operation in the window is busy 0.  Shard skew and the replica
pick show here."""
from perfbench import tracefile


def read(run):
    tr = run.trace
    if not tr:
        return None
    busy = [tracefile.busy_ns(ev, tr["t0"], tr["t1"])
            for ev in tr["devices"].values()]
    busy += [0.0] * (int(run.cell["chips"]) - len(busy))
    mean = sum(busy) / len(busy) if busy else 0.0
    if mean <= 0:
        return None
    return 100.0 * max(busy) / mean
