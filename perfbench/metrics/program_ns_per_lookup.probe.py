"""program_ns_per_lookup.probe: device-busy nanoseconds in the traced
window (union of every operation's interval: predict, last mile, health
stats, copies) per key answered in the window."""


def read(run):
    busy, n = run.busy_ns(), run.answered_in_window
    return busy / n if busy and n else None
