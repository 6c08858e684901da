"""Share of the traced window in which no operation ran on the device
(1 - busy / window, busy being the union of the operations' intervals),
averaged over the chips used."""


def read(run):
    busy = run.busy_ns()
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / (run.trace["t1"] - run.trace["t0"]))
