"""Serve driver: token generation and learned-index lookup serving.

Token mode (paged-KV continuous batching engine):

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --smoke \
        --requests 8 --max-new 8

Lookup mode (routes through repro.serve.lookup: async admission,
micro-batching, sharded fused dispatch — DESIGN.md §9):

    PYTHONPATH=src python -m repro.launch.serve --mode lookup \
        --dataset amzn --index rmi --requests 200 --keys-per-request 64

Ops surface (DESIGN.md §14): ``--metrics-port`` starts the stdlib HTTP
exporter (GET /metrics for Prometheus text, /metrics.json for the
structured lifetime+windowed document, /trace.json for the live Chrome
trace, /health.json + /alerts.json for index health, /healthz for
liveness), ``--trace-out`` records the whole run and writes a
Chrome-trace JSON openable in chrome://tracing or Perfetto,
``--metrics-jsonl`` appends periodic metrics snapshots for offline
analysis, and ``--slo-p99-ms`` arms the windowed error-budget tracking:

    PYTHONPATH=src python -m repro.launch.serve --mode lookup \
        --metrics-port 9100 --trace-out /tmp/lookup_trace.json \
        --slo-p99-ms 20

Index health (DESIGN.md §15): lookup serving is instrumented by default
(``--no-health`` turns it off) — the run summary prints the model-facing
health line (displacement p99 vs the error bound, drift score) and the
alert verdict; a run whose answers differ from the oracle exits
nonzero, and ``--doctor`` also exits nonzero when any alert is firing
at the end of the run, so a scripted health check is one command:

    PYTHONPATH=src python -m repro.launch.serve --mode lookup --doctor

Self-driving tuning (DESIGN.md §17): ``--autotune-daemon`` attaches the
shadow retuner — alert-triggered off-hot-path retunes under a
workload-aware objective, oracle-verified before the hot-swap — and
``--autotune-store DIR`` persists tuned specs across restarts;
``--doctor`` then also covers the daemon (last trigger/verdict in the
summary, nonzero exit on a dead retuner thread):

    PYTHONPATH=src python -m repro.launch.serve --mode lookup \\
        --autotune-daemon --autotune-store /tmp/specs --doctor
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax


def run_tokens(args):
    from repro.configs import get, get_smoke
    from repro.models import model as M
    from repro.serve.engine import ServeEngine

    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, max_batch=args.max_batch,
                         max_seq=args.max_seq)

    rng = np.random.default_rng(0)
    t0 = time.time()
    rids = [engine.submit(
        list(rng.integers(2, cfg.vocab, int(rng.integers(3, 10)))),
        max_new=args.max_new) for _ in range(args.requests)]
    outs = engine.run(max_steps=args.requests * (args.max_new + 12))
    dt = time.time() - t0
    n_tok = sum(len(v) for v in outs.values())
    for rid in rids:
        print(f"request {rid}: {outs[rid]}")
    print(f"\n{n_tok} tokens for {len(rids)} requests in {dt:.1f}s "
          f"({n_tok/dt:.1f} tok/s, continuous batching over "
          f"{args.max_batch} slots); kv pool util now "
          f"{engine.kv.alloc.utilization:.2f}")


def run_lookup(args):
    import contextlib

    from repro.core import base
    from repro.core.spec import IndexSpec
    from repro.data import sosd
    from repro.obs.export import JsonlMetricsLogger, MetricsServer
    from repro.serve.lookup import (LookupService, LookupServiceConfig,
                                    default_spec)

    keys = sosd.generate(args.dataset, args.n_keys, seed=1)
    # --spec takes one declarative IndexSpec (JSON) over the index name
    sp = (IndexSpec.from_json(args.spec) if args.spec
          else default_spec(args.index))
    at_cfg = None
    if args.autotune_daemon or args.autotune_store:
        from repro.autotune import AutotuneConfig
        at_cfg = AutotuneConfig(daemon=args.autotune_daemon,
                                store_dir=args.autotune_store)
    svc = LookupService(keys, LookupServiceConfig(
        spec=sp, max_batch=args.max_batch,
        deadline_ms=args.deadline_ms, executor=args.executor,
        shards=args.shards, replicas=args.replicas,
        trace=bool(args.trace_out), slo_p99_ms=args.slo_p99_ms,
        health=not args.no_health, autotune=at_cfg))
    print(f"serving spec: {svc.generation.spec.to_json()} "
          f"(executor={args.executor})")
    topo = getattr(svc.generation, "topology", None)
    if topo is not None:
        print(f"topology: {topo.describe()}")
    q = sosd.make_queries(keys, args.requests * args.keys_per_request, seed=2)

    with contextlib.ExitStack() as stack:
        if args.metrics_port is not None:
            server = stack.enter_context(
                MetricsServer(svc, port=args.metrics_port,
                              window_s=args.window_s))
            print(f"metrics: http://127.0.0.1:{server.port}/metrics "
                  f"(+ /metrics.json, /trace.json, /health.json, "
                  f"/alerts.json, /healthz)")
        if args.metrics_jsonl:
            stack.enter_context(JsonlMetricsLogger(
                svc, args.metrics_jsonl, interval_s=1.0,
                window_s=args.window_s))
        t0 = time.time()
        at_dead = False
        with svc:
            futs = [svc.submit(q[i * args.keys_per_request:
                                 (i + 1) * args.keys_per_request])
                    for i in range(args.requests)]
            outs = [f.result(timeout=120.0) for f in futs]
            # probe the retuner thread BEFORE stop() shuts it down on
            # purpose: --doctor must distinguish "died" from "stopped"
            at_dead = (svc.autotune is not None and svc.autotune.cfg.daemon
                       and not svc.autotune.alive)
        dt = time.time() - t0

    got = np.concatenate(outs)
    exact = bool(np.array_equal(got, base.lower_bound_oracle(keys, q)))
    snap = svc.metrics.snapshot()
    print(f"{len(q)} lookups / {args.requests} requests in {dt:.2f}s over "
          f"{svc.dispatcher.n_shards} shard(s): "
          f"{snap['lookups_per_s']/1e3:.1f} klookups/s, "
          f"{snap['batches']} batches, "
          f"occupancy {snap['mean_occupancy']:.2f}, "
          f"batch p99 {snap['p99_batch_ms']:.2f}ms, "
          f"queue p99 {snap['p99_queue_ms']:.2f}ms, "
          f"request p99 {snap['p99_request_ms']:.2f}ms, "
          f"cache hit rate {snap['cache_hit_rate']:.2f}")
    w = svc.metrics.windowed(args.window_s)
    line = (f"windowed({w['window_s']:.0f}s): p50 {w['p50_ms']:.2f}ms, "
            f"p99 {w['p99_ms']:.2f}ms, "
            f"{w['lookups_per_s']/1e3:.1f} klookups/s")
    if args.slo_p99_ms is not None:
        line += (f", SLO p99<{args.slo_p99_ms:.0f}ms: "
                 f"{w['slo_violations']} violations, "
                 f"budget burn {w['slo_budget_burn']:.2f}")
    print(line)
    if args.trace_out:
        svc.recorder.save(args.trace_out)
        print(f"wrote Chrome trace ({len(svc.recorder)} spans, "
              f"{svc.recorder.n_dropped} dropped) to {args.trace_out} — "
              f"open in chrome://tracing or https://ui.perfetto.dev")
    if args.metrics_jsonl:
        print(f"wrote metrics JSONL to {args.metrics_jsonl}")
    # §15 health verdict: evaluate the alert rules over the whole run
    events = svc.check_alerts(window_s=max(args.window_s, dt + 1.0))
    firing = svc.alerts.firing()
    if not args.no_health:
        h = svc.health_snapshot(max(args.window_s, dt + 1.0))
        gen = svc.generation
        max_err = int(getattr(gen, "max_err",
                              gen.plan.bounds.max_err))
        print(f"health: disp p99 {h['disp_p99']:.0f} of max_err "
              f"{max_err} "
              f"(bound utilization {h['bound_utilization_p99']:.2f}, "
              f"{h['disp_p99_ratio']:.2f}x build), "
              f"last-mile steps {h['mean_last_mile_steps']:.1f}, "
              f"drift TV {h['drift_tv']:.3f} over {h['drift_n']:.0f} "
              f"lookups")
    for e in events:
        print(f"alert {e['rule']} {e['state']}: {e['key']}={e['value']:.3g} "
              f"({e['op']} {e['threshold']:.3g}) — {e['action']}")
    print("alerts: " + (", ".join(firing) if firing else "none firing"))
    if svc.autotune is not None:
        st = svc.autotune.status()
        lt = st["last_trigger"]
        daemon_state = ("DEAD" if at_dead
                        else "up" if st["daemon"] else "off")
        print(f"autotune: daemon={daemon_state} "
              f"triggered={st['n_triggered']} swapped={st['n_swapped']} "
              f"rejected={st['n_rejected']}, "
              f"last trigger {lt['rule'] if lt else 'none'}, "
              f"last verdict {st['last_verdict'] or 'none'}")
        if at_dead:
            print(f"autotune: retuner thread died: "
                  f"{st['last_error'] or 'unknown error'}")
    print(f"exact vs lower_bound oracle: {exact}")
    if not exact or (args.doctor and (firing or at_dead)):
        raise SystemExit(1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("tokens", "lookup"), default="tokens")
    # token mode
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    # shared / lookup mode (default resolved per mode below: 4 decode
    # slots for tokens, 2048 keys per dispatch for lookups)
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--dataset", default="amzn",
                    choices=sorted(("amzn", "face", "osm", "wiki")))
    ap.add_argument("--index", default="rmi")
    ap.add_argument("--spec", default=None,
                    help="IndexSpec JSON (overrides --index), e.g. "
                         '\'{"index": "pgm", "hyper": {"eps": 32}}\'')
    ap.add_argument("--n-keys", type=int, default=200_000)
    ap.add_argument("--keys-per-request", type=int, default=64)
    ap.add_argument("--deadline-ms", type=float, default=2.0)
    ap.add_argument("--executor", choices=("sync", "async"), default="async",
                    help="lookup dispatch engine (DESIGN.md §13): the "
                         "continuous-batching async executor (default) "
                         "or the serial sync reference loop")
    ap.add_argument("--shards", type=int, default=1,
                    help="range-routed serving topology (DESIGN.md §16): "
                         "partition the key space into this many "
                         "equal-count ranges with per-shard indexes and "
                         "scatter/gather dispatch (1 = broadcast)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="read fan-out per shard (routed topology only): "
                         "each shard's lookups round-robin over this many "
                         "replica lanes")
    # ops surface (lookup mode, DESIGN.md §14)
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="start the HTTP metrics endpoint on this port "
                         "(0 = ephemeral): /metrics Prometheus text, "
                         "/metrics.json, /trace.json")
    ap.add_argument("--trace-out", default=None,
                    help="record request/lifecycle spans and write a "
                         "Chrome-trace JSON here (chrome://tracing, "
                         "Perfetto)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append one metrics snapshot per second to this "
                         "JSONL file (offline analysis)")
    ap.add_argument("--slo-p99-ms", type=float, default=None,
                    help="p99 latency SLO target: windowed snapshots "
                         "report violations + error-budget burn")
    ap.add_argument("--window-s", type=float, default=10.0,
                    help="rolling window the ops surfaces report over")
    ap.add_argument("--no-health", action="store_true",
                    help="disable index-health instrumentation "
                         "(DESIGN.md §15); reads dispatch the plain "
                         "executable with no stats reduction")
    ap.add_argument("--autotune-daemon", action="store_true",
                    help="start the shadow-retuner daemon (DESIGN.md "
                         "§17): workload-drift/error/SLO alerts trigger "
                         "an off-hot-path retune, verified bit-exact "
                         "against the oracle before hot-swapping")
    ap.add_argument("--autotune-store", default=None,
                    help="spec-artifact store directory: tuned specs "
                         "persist keyed by (dataset fingerprint, byte "
                         "budget, workload signature) so a restart on "
                         "the same workload skips the ladder sweep")
    ap.add_argument("--doctor", action="store_true",
                    help="one-shot health check: exit 1 when any alert "
                         "is firing or the autotune daemon thread died "
                         "during the run (inexact answers always exit 1)")
    args = ap.parse_args()

    from repro.launch import compile_cache

    compile_cache.enable()

    if args.mode == "lookup":
        if args.max_batch is None:
            args.max_batch = 2048
        run_lookup(args)
    else:
        if args.max_batch is None:
            args.max_batch = 4
        run_tokens(args)


if __name__ == "__main__":
    main()
