#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate at which
completions keep up with arrivals.

One set-up, then one window per offered rate, lowest first:

    python3 perfbench/sweep.py --workload books-pgm.get-zipf --seed 5 \\
        --seconds 5 --rates 5000,10000,20000,40000

Per rate it prints the share of the window's requests answered inside
the window, the answers' p50 and p99 (due time to answer), and how late
the generator ran.  Every answer is checked against the reference.  The
cell's traffic file then takes about four fifths of the knee as its
fixed ``rate_per_s``; benchmark runs never search for a rate.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def main(argv=None):
    import numpy as np

    from perfbench import harness, loadgen

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    args = ap.parse_args(argv)
    st = harness.set_up(harness.Bench(), args.workload, args.seed, False)
    if st.params["loop"] != "open":
        raise SystemExit("sweep: the cell's traffic is not an open loop")
    rows = []
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            params = dict(st.params, rate_per_s=rate)
            traffic = loadgen.make(params, st.keys, args.seed + 1 + i,
                                   args.seconds)
            w = loadgen.run(st.service, traffic, args.seconds)
            counts = harness._check(st.reference, st.keys, traffic, w,
                                    st.keys_per_request)
            lat = (w.t_done - w.t_due)[~np.isnan(w.t_done)] * 1e3
            rows.append({
                "rate_per_s": rate, "requests": len(w.answers),
                "answered_in_window_share":
                    float(np.count_nonzero(w.in_window)) / len(w.answers),
                "p50_ms": float(np.percentile(lat, 50)),
                "p99_ms": float(np.percentile(lat, 99)),
                "lag_p99_ms": float(np.percentile(w.lag, 99) * 1e3),
                "lag_max_ms": float(w.lag.max() * 1e3),
                **counts})
            print(json.dumps(rows[-1]), flush=True)
    finally:
        st.service.stop()
    return rows


if __name__ == "__main__":
    main()
