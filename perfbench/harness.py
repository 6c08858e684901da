"""Run one cell of `BENCHMARK.json` on the chip and print its result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell names a configuration and a traffic mix.  Everything is found by
name: the configuration's file is the one `BENCHMARK.json` gives, the
traffic mix is ``traffic/<name>.json``, the key-set recipe
``keysets/<recipe>.py``, the plain reference ``references/<name>.py``,
and each metric a reader of its own, ``e2e/<name>.py`` or
``metrics/<name>.py``, with a ``read(run)`` function that returns a
number or None (nothing to read: the metric is left out).

One run:

  set-up    draw the key set from the seed (`keygen`), build the
            `LookupService` the configuration states, warm every batch
            bucket the traffic can dispatch, draw the requests;
            ``setup_s`` runs from process start to the first request
            being due.
  window    drive ``LookupService.submit`` for ``--seconds``
            (`loadgen`); with ``--trace 1`` under the JAX profiler and
            the service's span recorder.
  check     once the window has closed, the peak memory read and the
            service stopped and freed: every answer is compared with the
            plain reference.  A run is correct when no answer is wrong,
            missing or an error.

With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics.  The last line of standard
output is the JSON result; the numbers compared, each beside its limit,
are the last lines of standard error and the result's last key.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from perfbench import tracefile

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
#: JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = CHECKOUT / ".jax_cache"
#: span ring large enough for every request of a traced window
TRACE_SPANS = 1 << 22


def require_devices(chips: int):
    """The first ``chips`` TPU devices; exits non-zero without them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"perfbench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s) "
              f"({devices[0].device_kind})", file=sys.stderr)
        raise SystemExit(3)
    return devices[:chips]


# ---------------------------------------------------------------------------
# Finding things by name
# ---------------------------------------------------------------------------
class Bench:
    """`BENCHMARK.json` and the directories its named files live in."""

    def __init__(self, root: Path = CHECKOUT, dirs=(HERE,)):
        self.root = Path(root)
        self.dirs = [Path(d) for d in dirs]
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _entry(self, section: str, name: str) -> dict:
        for e in self.spec[section]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {section} entry named {name!r}")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        return json.loads((self.root / self._entry("configs", name)["file"])
                          .read_text())

    def find(self, kind: str, name: str, ext: str) -> Path:
        for d in self.dirs:
            p = d / kind / f"{name}{ext}"
            if p.is_file():
                return p
        raise FileNotFoundError(f"no {kind}/{name}{ext} under "
                                f"{[str(d) for d in self.dirs]}")

    def traffic(self, name: str) -> dict:
        return json.loads(self.find("traffic", name, ".json").read_text())

    def module(self, kind: str, name: str):
        path = self.find(kind, name, ".py")
        mod_name = f"perfbench_{kind}_" + "".join(
            c if c.isalnum() else "_" for c in name)
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metrics(self, section: str, cell: str) -> List[dict]:
        """The metrics of ``section`` this cell reports."""
        return [m for m in self.spec[section]
                if "workloads" not in m or cell in m["workloads"]]


def peaks(kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# What the readers see
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """One run, as the metric readers see it."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    setup_s: float
    window: Any                       # loadgen.Window
    keys_per_request: int
    build: Dict[str, Any]             # the index build's meta numbers
    device_kind: str
    spans: Optional[list] = None      # program spans (--trace 1)
    trace: Optional[dict] = None      # reduced device trace (--trace 1)

    def peaks(self) -> dict:
        return peaks(self.device_kind)

    @property
    def answered_in_window(self) -> int:
        """Keys of the requests answered inside the window."""
        return int(np.count_nonzero(self.window.in_window)
                   * self.keys_per_request)

    def latencies_s(self) -> np.ndarray:
        """Due-to-answer seconds of every answered request."""
        w = self.window
        ok = ~np.isnan(w.t_done)
        return w.t_done[ok] - w.t_due[ok]

    def busy_ns(self) -> Optional[float]:
        """Device-busy ns of the traced window, averaged over the chips
        used; None without a device trace."""
        tr = self.trace
        if not tr or not tr["devices"]:
            return None
        return float(np.mean([tracefile.busy_ns(ev, tr["t0"], tr["t1"])
                              for ev in tr["devices"].values()]))

    def window_spans(self, name: str) -> list:
        """Program spans called ``name`` that start inside the window."""
        w = self.window
        return [s for s in self.spans or ()
                if s.name == name and w.t_start <= s.t0 <= w.t_end]


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
def _enable_compile_cache():
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _warm_buckets(max_batch: int, keys_per_request: int) -> tuple:
    """Every batch size the traffic can form: one request up to the
    batch cap (the dispatcher pads each to its power-of-two bucket)."""
    out, b = [], keys_per_request
    while b < max_batch:
        out.append(b)
        b *= 2
    return tuple(out) + (max(max_batch, keys_per_request),)


def service_config(cfg: dict, keys_per_request: int, trace: bool):
    """The `LookupServiceConfig` a configuration states.  Its
    ``service`` may give a topology, ``shards`` and ``replicas`` (1 and
    1 when absent: one key set broadcast over the cell's chips).  A
    routed batch splits over the shards by key, so a lane may get
    anything from one key to the whole batch: a routed service is given
    no warm buckets, and the program warms every lane at every bucket
    from its ``pad_quantum`` to ``max_batch``."""
    from repro.core.spec import IndexSpec
    from repro.serve.lookup import LookupServiceConfig

    s = cfg["service"]
    shards, replicas = int(s.get("shards", 1)), int(s.get("replicas", 1))
    max_batch = int(s["max_batch"])
    warm = () if shards > 1 else _warm_buckets(max_batch, keys_per_request)
    return LookupServiceConfig(
        spec=IndexSpec(cfg["index"]["name"], dict(cfg["index"]["hyper"]),
                       backend=s["backend"]),
        executor=s["executor"], max_batch=max_batch,
        deadline_ms=float(s["deadline_ms"]), health=bool(s["health"]),
        slots=int(s["slots"]), warm_buckets=warm, trace=trace,
        trace_capacity=TRACE_SPANS, shards=shards, replicas=replicas)


def build_service(cfg: dict, keys: np.ndarray, devices, keys_per_request: int,
                  trace: bool):
    from repro.serve.lookup import LookupService
    from repro.serve.lookup.dispatch import data_axis_mesh

    return LookupService(keys, service_config(cfg, keys_per_request, trace),
                         mesh=data_axis_mesh(devices))


def build_numbers(gen) -> Dict[str, Any]:
    """The index build's meta numbers that the readers use.  A routed
    generation has one build a shard: ``max_err`` and ``levels`` are the
    largest of the shards' (the kernel's window is bounded by the
    largest error, as `RoutedGeneration.max_err` says), and every other
    number is shard 0's, whose plan stands for the whole."""
    from repro.serve.lookup.registry import RoutedGeneration

    metas = ([g.build.meta for g in gen.shards]
             if isinstance(gen, RoutedGeneration) else [gen.build.meta])
    out = {key: v for key, v in metas[0].items()
           if isinstance(v, (int, float))}
    for key in ("max_err", "levels"):
        if key in out:
            out[key] = max(m[key] for m in metas)
    return out


def _reduce_trace(log_dir: str, anchor_pc: float, t_start: float,
                  t_end: float) -> dict:
    """The traced window's device operations on the profiler's clock,
    with the offset that maps host ``perf_counter`` seconds onto it."""
    raw = tracefile.read(log_dir)
    anchors = [e for e in raw["host"] if e[0] == "perfbench.anchor"]
    if not anchors:
        raise RuntimeError("the trace holds no perfbench.anchor annotation")
    offset = anchors[0][1] - anchor_pc * 1e9
    t0, t1 = t_start * 1e9 + offset, t_end * 1e9 + offset
    return {"devices": raw["devices"], "t0": t0, "t1": t1, "offset": offset}


def _check(reference, keys, traffic, window, k: int) -> dict:
    """Compare every answer with the plain reference; returns the
    counts that decide ``correct``."""
    answered = [i for i, a in enumerate(window.answers) if a is not None]
    unanswered = len(window.answers) - len(answered) - window.errors
    wrong_keys = wrong_requests = 0
    if answered:
        req = window.request[answered]
        uniq, inv = np.unique(req, return_inverse=True)
        want = reference.answers(keys, traffic.queries[uniq].ravel()
                                 ).reshape(len(uniq), k)
        for row, i in zip(inv, answered):
            got = np.asarray(window.answers[i])
            bad = (k if got.shape != (k,)
                   else int(np.count_nonzero(got != want[row])))
            wrong_keys += bad
            wrong_requests += bad > 0
    return {"wrong_answers": wrong_keys, "wrong_requests": wrong_requests,
            "unanswered": unanswered, "errors": window.errors}


def _cover(gaps: list, intervals: list) -> list:
    """How much of each of the sorted disjoint ``gaps`` the sorted
    disjoint ``intervals`` cover, in one sweep: an interval that ends
    before a gap starts ends before every later gap starts too.  Each
    sum adds the overlaps in the order `tracefile.overlap` does."""
    out, j = [], 0
    for a, b in gaps:
        while j < len(intervals) and intervals[j][1] <= a:
            j += 1
        total, k = 0, j
        while k < len(intervals) and intervals[k][0] < b:
            x, y = intervals[k]
            total += max(0.0, min(y, b) - max(x, a))
            k += 1
        out.append(total)
    return out


def _idle_by_host(trace: dict, spans: list, ops: list) -> list:
    """Device idle time in the window, by what the host was doing: in a
    `launch` (pad, place, enqueue), `finalize` (wait, copy back,
    resolve) or `compile` span; outside them with requests admitted and
    not yet launched; or with no request pending.  Each gap goes to the
    activity that covers most of it."""
    off = trace["offset"]

    def union(items):
        return tracefile.merged([("", a * 1e9 + off, (b - a) * 1e9)
                                 for a, b in items], trace["t0"], trace["t1"])

    acts = {name: union([(s.t0, s.t0 + s.dur) for s in spans
                         if s.name == name])
            for name in ("launch", "finalize", "compile")}
    acts["requests queued"] = union(
        [(s.t0, s.t0 + s.args["queue_us"] / 1e6) for s in spans
         if s.name == "request" and s.args])
    gaps = tracefile.gaps(ops, trace["t0"], trace["t1"])
    covers = {n: _cover(gaps, iv) for n, iv in acts.items()}
    tot: Dict[str, float] = {}
    for i, (a, b) in enumerate(gaps):
        cover = {n: c[i] for n, c in covers.items()}
        best = max(cover, key=cover.get)
        label = best if cover[best] > 0 else "no request pending"
        tot[label] = tot.get(label, 0.0) + (b - a) / 1e9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:10]


@dataclasses.dataclass
class Setup:
    """A cell made ready to serve: its key set, and the started service."""

    cell: dict
    config: dict
    params: dict
    keys_per_request: int
    reference: Any
    keys: np.ndarray
    service: Any
    devices: list
    build: Dict[str, Any]
    seconds: Dict[str, float]       # generate, build, warm


def set_up(bench: Bench, name: str, seed: int, trace: bool) -> Setup:
    """Generate the key set, build the service the configuration states,
    its topology included, and warm every batch bucket the traffic can
    dispatch.  The readers' build numbers come from `build_numbers`: of
    a routed generation, ``max_err`` and ``levels`` are the largest of
    its shards', every other number shard 0's."""
    cell = bench.workload(name)
    devices = require_devices(int(cell["chips"]))
    _enable_compile_cache()
    cfg = bench.config(cell["config"])
    params = bench.traffic(cell["traffic"])
    k = int(params["keys_per_request"])
    recipe = bench.module("keysets", cfg["keys"]["recipe"])
    took = {}
    t = time.perf_counter()
    keys = recipe.generate(int(cfg["keys"]["n"]), seed)
    took["generate"] = time.perf_counter() - t
    t = time.perf_counter()
    svc = build_service(cfg, keys, devices, k, trace)
    took["build"] = time.perf_counter() - t
    t = time.perf_counter()
    svc.start()
    took["warm"] = time.perf_counter() - t
    return Setup(cell, cfg, params, k,
                 bench.module("references", cfg["reference"]), keys, svc,
                 devices, build_numbers(svc.generation), took)


def run_cell(bench: Bench, name: str, seed: int, seconds: float,
             trace: bool, t_process: float, control=None) -> dict:
    """One run of a cell; returns its result line.  ``control`` (used by
    `control.py`, never by a benchmark run) maps ``(keys, queries[r, k])``
    to the control's answers, which are checked against the reference in
    place of the program's, for the very requests the window answered."""
    import jax

    from perfbench import loadgen

    st = set_up(bench, name, seed, trace)
    cell, cfg, params, k = st.cell, st.config, st.params, st.keys_per_request
    keys, svc, devices, build = st.keys, st.service, st.devices, st.build
    reference = st.reference
    traffic = loadgen.make(params, keys, seed, seconds)
    misses0 = svc.exec_cache.counters()[1]

    log_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    anchor = {}

    def on_start():
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("perfbench.anchor"):
                anchor["pc"] = time.perf_counter()

    try:
        window = loadgen.run(svc, traffic, seconds, on_start=on_start)
    finally:
        if trace:
            jax.profiler.stop_trace()
    serving_compiles = svc.exec_cache.counters()[1] - misses0
    setup_s = window.t_start - t_process
    mem = [d.memory_stats() or {} for d in devices]
    peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
    in_use = max(m.get("bytes_in_use", 0) for m in mem)
    svc.stop()
    spans = svc.recorder.spans() if trace else None
    if svc.warm_failures:
        raise RuntimeError(f"warm-up failed: {svc.last_warm_error!r}")
    took = st.seconds
    del svc, st
    gc.collect()

    counts = _check(reference, keys, traffic, window, k)
    control_counts = None
    if control is not None:
        uniq = np.unique(window.request)
        ctl = control(keys, traffic.queries[uniq])
        row = {int(r): i for i, r in enumerate(uniq)}
        got = [None if a is None else ctl[row[int(r)]]
               for a, r in zip(window.answers, window.request)]
        control_counts = _check(reference, keys, traffic,
                                dataclasses.replace(window, answers=got), k)
    kind = devices[0].device_kind
    run = Run(cell=cell, config=cfg, traffic=params, seed=seed,
              seconds=seconds, setup_s=setup_s, window=window,
              keys_per_request=k, build=build, device_kind=kind,
              spans=spans)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        run.trace = _reduce_trace(log_dir, anchor["pc"], window.t_start,
                                  window.t_end)
        shutil.rmtree(log_dir, ignore_errors=True)
        t0, t1 = run.trace["t0"], run.trace["t1"]
        device["busy_s"] = (run.busy_ns() or 0.0) / 1e9
        device["window_s"] = (t1 - t0) / 1e9
        ops = [e for ev in run.trace["devices"].values() for e in ev]
        breakdown = {"device_ops": [list(x) for x in
                                    tracefile.top_ops(ops, t0, t1)],
                     "idle_gaps": [list(x) for x in
                                   _idle_by_host(run.trace, spans, ops)]}

    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench.metrics(section, name):
        reader = bench.module("metrics" if trace else "e2e", m["name"])
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    lat = run.latencies_s() * 1e3
    lag = window.lag
    print(f"perfbench: {name} seed={seed} keys={keys.size} "
          f"generate_s={took['generate']:.3f} "
          f"build_s={took['build']:.3f} "
          f"warm_s={took['warm']:.3f} setup_s={setup_s:.3f} "
          f"index={json.dumps(build)}", file=sys.stderr)
    print(f"perfbench: requests={len(window.answers)} "
          f"answered_in_window={int(np.count_nonzero(window.in_window))} "
          f"latency_ms p50={np.percentile(lat, 50) if lat.size else 0:.4f} "
          f"p99={np.percentile(lat, 99) if lat.size else 0:.4f} "
          f"serving_compiles_in_window={serving_compiles} "
          f"bytes_in_use={in_use} peak_bytes_in_use={peak}",
          file=sys.stderr)
    if lag is not None:
        print(f"perfbench: generator lag ms p50={np.percentile(lag, 50)*1e3:.4f}"
              f" p99={np.percentile(lag, 99)*1e3:.4f} "
              f"max={lag.max()*1e3:.4f}", file=sys.stderr)
    checks = {n: {"value": v, "limit": 0} for n, v in counts.items()}
    for n, c in checks.items():
        print(f"check {n} {c['value']} limit {c['limit']}", file=sys.stderr)
    failed = counts["errors"] + counts["unanswered"] + counts["wrong_requests"]
    result = {"correct": failed == 0 and len(window.answers) > 0,
              "attempted": len(window.answers), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control_counts is not None:
        result["control"] = control_counts
    result["checks"] = checks
    return result


def main(argv=None, t_process: Optional[float] = None,
         bench: Optional[Bench] = None) -> dict:
    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(bench if bench is not None else Bench(), args.workload,
                      args.seed, args.seconds, bool(args.trace), t_process)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return result
