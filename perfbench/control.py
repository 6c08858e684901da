#!/usr/bin/env python3
"""The control of `correct`: the check must fail an answer path that
breaks the configuration's guarantee.

The configurations state exact lower-bound ranks over 64-bit keys.  The
control is the plain reference put in the program's place one precision
lower, the step that would tempt a later change on a 32-bit machine:
keys and queries rounded to float32, then the lower bound taken on the
device.  `run_cell` drives a normal window at the cell's own load, then
checks the control's answers for the very requests the window answered,
with the same comparison that decides ``correct``.  Per seed this prints
the program's counts and the control's:

    python3 perfbench/control.py --workload books-pgm.probe1024 \\
        --seeds 11,12,13 --seconds 3

No benchmark run runs this.  `tests/test_perfbench_control.py` keeps it
at a size a test run holds.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

#: queries per device call of the control
CHUNK = 1 << 20


def float32_lower_bound(keys, queries):
    """Lower-bound ranks over float32-rounded keys and queries, on the
    device: the reference one precision down."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    kf = jnp.asarray(keys).astype(jnp.float32)
    search = jax.jit(lambda k, q: jnp.searchsorted(k, q.astype(jnp.float32),
                                                   side="left"))
    flat = np.asarray(queries, np.uint64).ravel()
    out = np.empty(flat.size, np.int64)
    for s in range(0, flat.size, CHUNK):
        q = flat[s:s + CHUNK]
        pad = np.full(CHUNK, q[0], np.uint64)
        pad[:q.size] = q
        out[s:s + q.size] = np.asarray(search(kf, jnp.asarray(pad)))[:q.size]
    del kf
    return out.reshape(np.shape(queries))


def main(argv=None):
    from perfbench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    bench = harness.Bench()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(bench, args.workload, seed, args.seconds,
                             False, T_PROCESS, control=float32_lower_bound)
        rows.append({"seed": seed, "correct": r["correct"],
                     "program": {n: c["value"] for n, c in r["checks"].items()},
                     "control": r["control"]})
        print(json.dumps(rows[-1]), flush=True)
    return rows


if __name__ == "__main__":
    main()
