"""bounded_search_roofline: the least time the chip needs for the bytes
the last-mile search of the window's lookups must move (query, the key
window the index's error bound guarantees, answer; `roofline.py`) at
peak HBM bandwidth, over the summed device time of the `bounded_search`
kernel's operations.  In percent; bytes bound it, not operations."""
from perfbench import roofline, tracefile


def read(run):
    tr = run.trace
    n = run.answered_in_window
    if not tr or n == 0:
        return None
    ns = sum(tracefile.op_ns(ev, "bounded_search", tr["t0"], tr["t1"])
             for ev in tr["devices"].values())
    if ns <= 0:
        return None
    least_ns = (n * roofline.search_bytes(run.build)
                / run.peaks()["hbm_bytes_per_s"] * 1e9)
    return 100.0 * least_ns / ns
