"""Share of the traced window in which no operation ran on the device
while the dispatch thread was in a `pin`, `gather` or `launch` span,
averaged over the chips used.  The spans are put on the profiler's clock
by the anchor offset; a program without `pin` spans reports nothing."""
from perfbench import scopetrace


def read(run):
    tr = run.trace
    spans = [s for s in run.spans or () if s.name in scopetrace.DISPATCH]
    if not tr or not tr["devices"] or not any(s.name == "pin"
                                               for s in spans):
        return None
    off, t0, t1 = tr["offset"], tr["t0"], tr["t1"]
    open_ = [(s.t0 * 1e9 + off, (s.t0 + s.dur) * 1e9 + off) for s in spans]
    idle = [scopetrace.idle_while(ev, open_, t0, t1)
            for ev in tr["devices"].values()]
    return 100.0 * sum(idle) / len(idle) / (t1 - t0)
