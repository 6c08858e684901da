"""Run every paper-table benchmark; print ``name,us_per_call,derived`` CSV.

One line per benchmark module (aggregate timing) plus detailed CSVs under
benchmarks/results/.  ``SOSD_N`` / ``SOSD_Q`` env vars scale the workload
(defaults keep single-core CPU runtime reasonable).
"""
from __future__ import annotations

import os
import sys
import time

# runnable both as `python -m benchmarks.run` and `python benchmarks/run.py`
# (repo root for the `benchmarks` package, src/ for `repro` when PYTHONPATH
# wasn't exported)
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
if "repro" not in sys.modules:
    sys.path.insert(0, os.path.join(_ROOT, "src"))

os.environ.setdefault("SOSD_N", "200000")
os.environ.setdefault("SOSD_Q", "50000")


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", choices=("jnp", "pallas"),
                    default=os.environ.get("SOSD_BACKEND", "jnp"),
                    help="LookupPlan backend for every lookup benchmark "
                         "(pallas = kernel path, interpret mode on CPU)")
    ap.add_argument("--autotune", type=int, default=None, metavar="BYTES",
                    help="add the budget-tuner rows: per-dataset "
                         "spec+backend selection under this hard byte "
                         "budget (pareto_autotune), and compaction-retuned "
                         "mixed-workload cells")
    args = ap.parse_args()
    # _common reads the env at import; set it before the imports below
    os.environ["SOSD_BACKEND"] = args.backend

    from repro.launch import compile_cache

    compile_cache.enable()
    from benchmarks import (batching_effects, build_times, explain, key_size,
                            mixed_workload, moe_dispatch, pareto,
                            parallel_scaling, scaling, search_fn,
                            serve_throughput)

    print(f"# backend={args.backend}")
    print("name,us_per_call,derived")
    jobs = [
        ("pareto_fig7", pareto.run, lambda rows: pareto.pareto_summary(rows)),
        ("scaling_fig9", scaling.run, lambda rows: f"{len(rows)}pts"),
        ("key_size_fig10", key_size.run, lambda rows: f"{len(rows)}pts"),
        ("search_fn_fig11", search_fn.run, lambda rows: f"{len(rows)}pts"),
        ("explain_fig12", lambda: explain.run()[1],
         lambda s: f"R2={s['multi_metric_r2']}"),
        ("batching_fig14_15", batching_effects.run,
         lambda rows: f"max_slowdown={max(r[-1] for r in rows)}"),
        ("parallel_fig16", parallel_scaling.run, lambda rows: f"{len(rows)}pts"),
        ("build_times_fig17", build_times.run, lambda rows: f"{len(rows)}pts"),
        ("moe_dispatch_technique", moe_dispatch.run,
         lambda rows: "; ".join(f"{r[0]}:{r[2]}x" for r in rows
                                if r[1] == "dense/sorted-flop-ratio")),
        ("serve_throughput", serve_throughput.run,
         lambda rows: f"verified={sum(r['verified_vs_core'] for r in rows)}"
                      f"/{len(rows)}"),
        ("mixed_workload", mixed_workload.run,
         lambda rows: f"verified={sum(r['verified_vs_oracle'] for r in rows)}"
                      f"/{len(rows)};compactions="
                      f"{sum(r['compactions'] for r in rows)}"),
    ]
    if args.autotune is not None:
        jobs.append((
            "pareto_autotune",
            lambda: pareto.run_autotune(budget=args.autotune),
            lambda rows: "; ".join(f"{r[0]}:{r[1]}@{r[3]}B" for r in rows)))
        jobs.append((
            "mixed_workload_autotuned",
            lambda: mixed_workload.run(autotune=args.autotune),
            lambda rows: f"verified="
                         f"{sum(r['verified_vs_oracle'] for r in rows)}"
                         f"/{len(rows)};retuned="
                         f"{sum(r['retuned'] for r in rows)}"))
    for name, fn, derive in jobs:
        t0 = time.perf_counter()
        result = fn()
        us = (time.perf_counter() - t0) * 1e6
        try:
            derived = derive(result)
        except Exception:  # noqa: BLE001
            derived = "?"
        print(f"{name},{us:.0f},{str(derived).replace(',', ';')}", flush=True)

    # roofline table if the dry-run artifacts exist
    path = "benchmarks/results/dryrun_single_pod.json"
    if os.path.exists(path):
        from benchmarks import roofline

        print("\n== roofline (single pod 16x16) ==")
        print(roofline.table(path))


if __name__ == "__main__":
    main()
