"""program_roofline.probe: the least time the chip needs for the bytes a
whole lookup must move (query, the index model's parameters on the
predict path, the guaranteed key window, answer; `roofline.py`) at peak
HBM bandwidth, over the device-busy time of the traced window.  In
percent.  Bounds the kernel's share from the whole program's side: a
change that moves work out of the kernel still shows here."""
from perfbench import roofline


def read(run):
    busy, n = run.busy_ns(), run.answered_in_window
    if not busy or not n:
        return None
    least_ns = (n * roofline.lookup_bytes(run.config, run.build)
                / run.peaks()["hbm_bytes_per_s"] * 1e9)
    return 100.0 * least_ns / busy
