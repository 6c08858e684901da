#!/usr/bin/env python3
"""Bring-up smoke: serve SOSD-scale lookups on one TPU chip and check them.

    python chip_smoke.py               # one chip: jnp, then Pallas backend
    python chip_smoke.py --four-chips  # routed and broadcast over 4 chips

One process; nothing here starts another.  The smoke generates the amzn
surrogate at SOSD's published scale (200M sorted uint64 keys, 1.6 GB;
SOSD, arXiv 1911.13014, §3) from a fixed seed, builds a `LookupService`
over it — the async executor with index health on, as ``python -m
repro.launch.serve --mode lookup`` builds it — and serves a few thousand
64-key reads plus a batch of YCSB-E style scans (length uniform in
1..100).  Every answer is compared with ``np.searchsorted`` on the host.

Each phase prints one line: keys, key and operand bytes (one copy), the
index's ``max_err``, build and compile seconds, how many queries the
kernel's window fallback served, the answers checked and each device's
``bytes_in_use`` and ``peak_bytes_in_use``.  Times are host-clock set-up
times, not a benchmark.  The last line is ``{"ok": true, "device": {...}}``; any
mismatch or error exits non-zero without it, and so does a host where
JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SOSD_N = 200_000_000          # SOSD's key count per dataset
DATASET = "amzn"
SEED = 1
#: PGM at its default error bound: its window (max_err 154 on this
#: surrogate at 200M keys) fits the kernel's 2048-key data tile.  The
#: serving-default RMI does not: on amzn its bound spans most of the keys.
INDEX, HYPER = "pgm", {"eps": 64}
KEYS_PER_REQUEST = 64
SCAN_MAX = 100                # YCSB-E: scan length uniform in 1..100
#: mode -> (phases, max_batch, scan requests).  One chip serves with the
#: batch cap of ``repro.launch.serve --mode lookup``.  Four chips run
#: eight routed lanes, each of which compiles its own programs per batch
#: bucket and scan length, so that mode keeps to fewer of both.
MODES = {
    "one_chip": ([("one_chip", "jnp", {}), ("one_chip", "pallas", {})],
                 2048, 16),
    "four_chips": ([("routed_4x2", "pallas", dict(shards=4, replicas=2)),
                    ("broadcast", "pallas", {})], 256, 2),
}


def require_tpu():
    """The devices to run on; exits non-zero, before any work, unless
    JAX's first device is a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {devices[0].platform!r} "
              f"({devices[0].device_kind})", file=sys.stderr)
        raise SystemExit(2)
    return devices


def _scan_oracle(keys, pos, m):
    import numpy as np

    out = np.full((len(pos), m), np.iinfo(np.uint64).max, np.uint64)
    for i, p in enumerate(pos):
        seg = keys[p:p + m]
        out[i, :seg.size] = seg
    return out


def _memory(devices):
    stats = [d.memory_stats() or {} for d in devices]
    return ([s.get("bytes_in_use", -1) for s in stats],
            [s.get("peak_bytes_in_use", -1) for s in stats])


def run_phase(name, keys, reads, scans, devices, mesh, backend,
              max_batch, **topo):
    """Build, warm and serve one service; returns its report line.
    Raises on any wrong answer."""
    import numpy as np
    import jax

    from repro.core.spec import IndexSpec
    from repro.serve.lookup import LookupService, LookupServiceConfig
    from repro.serve.lookup.registry import RoutedGeneration

    cfg = LookupServiceConfig(
        spec=IndexSpec(INDEX, dict(HYPER), backend=backend),
        executor="async", health=True, max_batch=max_batch,
        deadline_ms=2.0, **topo)
    t0 = time.perf_counter()
    svc = LookupService(keys, cfg, mesh=mesh)
    gen = svc.generation
    shards = gen.shards if isinstance(gen, RoutedGeneration) else (gen,)
    jax.block_until_ready([s.data for s in shards])
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    svc.start()                                 # warm-up compiles
    compile_s = time.perf_counter() - t0
    try:
        t0 = time.perf_counter()
        futs = [svc.submit(r) for r in reads]
        sfuts = [svc.scan(q, m) for q, m in scans]
        got = [f.result(timeout=900.0) for f in futs]
        sgot = [f.result(timeout=900.0) for f in sfuts]
        serve_s = time.perf_counter() - t0
    finally:
        svc.stop()
    if svc.warm_failures:
        raise RuntimeError(f"{name}: warm-up failed: {svc.last_warm_error!r}")

    q = np.concatenate(reads)
    want = np.searchsorted(keys, q)
    pos = np.concatenate(got)
    if not np.array_equal(pos, want):
        bad = int(np.count_nonzero(pos != want))
        raise AssertionError(f"{name}: {bad} of {q.size} reads differ "
                             f"from np.searchsorted")
    n_scan = 0
    for (sq, m), (spos, win) in zip(scans, sgot):
        swant = np.searchsorted(keys, sq)
        if not (np.array_equal(spos, swant)
                and np.array_equal(win, _scan_oracle(keys, swant, m))):
            raise AssertionError(f"{name}: scan of length {m} differs")
        n_scan += sq.size

    plans = [s.plan for s in shards]
    max_err = max(p.bounds.max_err for p in plans)
    n_answers = q.size + n_scan
    # which last-mile path served: the choice is static per plan, so
    # every query routed to a plan whose window is too wide took the
    # fallback
    if backend == "pallas":
        wide = [i for i, p in enumerate(plans) if not p.kernel_serves(backend)]
        all_q = np.concatenate([q] + [sq for sq, _ in scans])
        shard_of = (gen.topology.route(all_q) if len(plans) > 1
                    else np.zeros(all_q.size, np.int64))
        fallback = int(np.isin(shard_of, wide).sum())
        if fallback == n_answers:
            raise AssertionError(f"{name}: the window fallback served "
                                 f"every query; the kernel never ran")
    # one copy of each; where the copies went shows in bytes_in_use
    op_bytes = sum(int(x.nbytes) for p in plans
                   for x in jax.tree.leaves(p.operands(backend)))
    in_use, peak = _memory(devices)
    hits, misses = svc.exec_cache.counters()
    return (f"phase {name}: backend={backend} n_keys={keys.size} "
            f"key_bytes={keys.nbytes} operand_bytes={op_bytes} "
            f"max_err={max_err} "
            f"build_s={build_s:.1f} compile_s={compile_s:.1f} "
            f"serve_s={serve_s:.1f}(host clock, not a benchmark) "
            f"window_fallback_queries="
            f"{fallback if backend == 'pallas' else 'n/a(jnp)'} "
            f"answers_checked={n_answers} serving_compiles={misses} "
            f"bytes_in_use={in_use} peak_bytes_in_use={peak}")


def make_workload(keys, requests: int, scans: int):
    """``requests`` 64-key reads (80% present keys) and ``scans`` scan
    requests of 16 keys each with a YCSB-E length."""
    import numpy as np

    from repro.data import sosd

    q = sosd.make_queries(keys, requests * KEYS_PER_REQUEST, seed=SEED + 1)
    reads = np.split(q, requests)
    rng = np.random.default_rng(SEED + 2)
    sq = sosd.make_queries(keys, scans * 16, seed=SEED + 3)
    lengths = rng.integers(1, SCAN_MAX + 1, scans)
    return reads, list(zip(np.split(sq, scans), (int(m) for m in lengths)))


def main(argv=None, *, n_keys: int = SOSD_N, requests: int = 2000) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip path on four chips: a "
                         "routed topology (4 shards, 2 replicas each) and "
                         "broadcast dispatch, both Pallas, on the same "
                         "key set")
    args = ap.parse_args(argv)
    devices = require_tpu()

    from repro.launch import compile_cache

    compile_cache.enable()
    from repro.data import sosd
    from repro.serve.lookup.dispatch import data_axis_mesh

    count = 4 if args.four_chips else 1
    phases, max_batch, scans = MODES["four_chips" if args.four_chips
                                     else "one_chip"]
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: needs {count} devices, "
                         f"JAX found {len(devices)}")
    devices = devices[:count]
    mesh = data_axis_mesh(devices)

    t0 = time.perf_counter()
    keys = sosd.generate(DATASET, n_keys, seed=SEED)
    reads, scan_reqs = make_workload(keys, requests, scans)
    print(f"data: {DATASET} surrogate, {keys.size} sorted uint64 keys, "
          f"seed {SEED}, made in {time.perf_counter() - t0:.1f}s; "
          f"{len(reads)} reads x {KEYS_PER_REQUEST} keys, "
          f"{len(scan_reqs)} scans", flush=True)

    for name, backend, topo in phases:
        line = run_phase(name, keys, reads, scan_reqs, devices, mesh,
                         backend, max_batch, **topo)
        print(line, flush=True)
        gc.collect()            # free this phase's device arrays

    d = devices[0]
    result = {"ok": True, "device": {"platform": d.platform,
                                     "kind": d.device_kind,
                                     "count": len(devices)}}
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
