"""rmi_fetch_roofline: the least time the chip needs for the bytes the
stage-2 fetch of the window's lookups must move (one stage-2 row a
lookup; `fetch_bytes.py`) at peak HBM bandwidth, over the summed device
time of the `rmi_stage2_fetch` kernel's operations, summed over chips.
In percent; bytes bound it, not operations."""
from perfbench import fetch_bytes, tracefile


def read(run):
    tr = run.trace
    n = run.answered_in_window
    if not tr or n == 0:
        return None
    ns = sum(tracefile.op_ns(ev, "rmi_stage2_fetch", tr["t0"], tr["t1"])
             for ev in tr["devices"].values())
    if ns <= 0:
        return None
    least_ns = (n * fetch_bytes.fetch_bytes(run.config)
                / run.peaks()["hbm_bytes_per_s"] * 1e9)
    return 100.0 * least_ns / ns
