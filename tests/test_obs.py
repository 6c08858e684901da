"""Observability layer (`repro.obs`, DESIGN.md §14): histograms,
rolling windows, the span recorder, the exporters, and the serve-path
integration contracts (trace-vs-histogram p99 reconciliation, the
mid-run p99 shift that windows surface and lifetime aggregates hide)."""
import json
import threading
import urllib.request

import numpy as np
import pytest

from repro.obs.export import (JsonlMetricsLogger, MetricsServer,
                              metrics_payload, prometheus_text)
from repro.obs.trace import SpanRecorder, maybe_span
from repro.obs.windows import LatencyHistogram, WindowedMetrics
from repro.serve.lookup.metrics import ServiceMetrics


# ---------------------------------------------------------------------------
# LatencyHistogram: bisect record, quantile edges, merge
# ---------------------------------------------------------------------------
def _linear_scan_bucket(hist, seconds):
    """The pre-bisect reference: first i with seconds < bounds[i]."""
    for i, b in enumerate(hist.bounds):
        if seconds < b:
            return i
    return len(hist.bounds)


def test_bucket_index_matches_linear_scan_reference():
    h = LatencyHistogram()
    probes = [0.0, 1e-9, 1e-6, 1.05e-6, 3.7e-4, 0.01, 1.0, 80.0, 1e4]
    probes += list(h.bounds[::37])          # exact bound values too
    probes += [b * (1 + 1e-12) for b in h.bounds[::53]]
    for s in probes:
        assert h.bucket_index(s) == _linear_scan_bucket(h, s), s


def test_quantile_empty_histogram_is_zero():
    h = LatencyHistogram()
    assert h.quantile(0.5) == 0.0
    assert h.quantile(0.99) == 0.0
    assert h.mean == 0.0


def test_quantile_overflow_bucket_is_inf():
    h = LatencyHistogram()
    h.record(1e6)                           # way past the last bound
    assert h.quantile(0.99) == float("inf")
    # mixed: the sub-bound mass keeps sub-bound quantiles finite
    for _ in range(99):
        h.record(1e-3)
    assert h.quantile(0.50) < float("inf")
    assert h.quantile(0.999) == float("inf")


def test_histogram_merge_equals_flat_recording():
    rng = np.random.default_rng(0)
    obs = rng.lognormal(mean=-6.0, sigma=1.5, size=2_000)
    flat, a, b = LatencyHistogram(), LatencyHistogram(), LatencyHistogram()
    for i, s in enumerate(obs):
        flat.record(s)
        (a if i % 2 else b).record(s)
    a.merge(b)
    assert a.counts == flat.counts
    assert a.n == flat.n
    assert a.total_s == pytest.approx(flat.total_s)
    assert a.quantile(0.99) == flat.quantile(0.99)


def test_histogram_merge_rejects_mismatched_buckets():
    with pytest.raises(ValueError):
        LatencyHistogram().merge(LatencyHistogram(n_buckets=100))


# ---------------------------------------------------------------------------
# WindowedMetrics: merge-at-read == flat, mid-run shift, SLO burn
# ---------------------------------------------------------------------------
def test_windowed_merge_matches_flat_histogram():
    """Summing per-slot sub-histograms at read time must reproduce the
    flat histogram of the same observations exactly."""
    rng = np.random.default_rng(1)
    w = WindowedMetrics(slot_s=0.5, n_slots=64, clock=lambda: 0.0)
    flat = LatencyHistogram()
    t = 1000.0
    for s in rng.lognormal(mean=-7.0, sigma=1.0, size=3_000):
        t += rng.uniform(0, 0.01)           # spread over ~30s of slots
        w.record(s, units=3, t=t)
        flat.record(s)
    hist, units, _, _ = w.merged(window_s=w.max_window_s, t=t)
    assert hist.counts == flat.counts
    assert units == 3 * flat.n
    assert hist.quantile(0.99) == flat.quantile(0.99)


def test_windowed_snapshot_surfaces_p99_shift_lifetime_hides():
    """THE pinned §14.2 acceptance property: a mid-run latency shift is
    visible in the trailing-window p99 while the lifetime aggregate —
    dominated by the long fast prefix — still reports the old p99."""
    w = WindowedMetrics(slot_s=0.5, n_slots=240)
    lifetime = LatencyHistogram()
    fast, slow = 1e-3, 50e-3
    t = 5000.0
    for i in range(10_000):                 # long healthy prefix
        w.record(fast, t=t + i * 1e-3)
        lifetime.record(fast)
    t2 = t + 60.0                           # regression: the last ~2s
    for i in range(50):
        w.record(slow, t=t2 + i * 0.04)
        lifetime.record(slow)
    # lifetime: 50/10050 slow observations < 1% — p99 still reads fast
    assert lifetime.quantile(0.99) < 2 * fast
    # trailing window: only the regressed traffic — p99 reads the shift
    recent = w.snapshot(window_s=5.0, t=t2 + 2.0)
    assert recent["n"] == 50
    assert recent["p99_ms"] >= slow * 1e3
    # ...and the full-history window agrees with the lifetime aggregate
    full = w.snapshot(window_s=w.max_window_s, t=t2 + 2.0)
    assert full["p99_ms"] == pytest.approx(lifetime.quantile(0.99) * 1e3)


def test_windowed_slot_recycling_drops_stale_slots():
    w = WindowedMetrics(slot_s=1.0, n_slots=4, clock=lambda: 0.0)
    w.record(1e-3, t=100.0)
    assert w.snapshot(window_s=4.0, t=100.0)["n"] == 1
    # 4 slots later the ring position recycles; old slot is unreachable
    w.record(2e-3, t=104.0)
    snap = w.snapshot(window_s=4.0, t=104.0)
    assert snap["n"] == 1
    assert snap["p99_ms"] >= 2.0


def test_windowed_slo_violations_and_budget_burn():
    w = WindowedMetrics(slot_s=1.0, n_slots=16, slo_p99_ms=10.0,
                        slo_budget=0.01, clock=lambda: 0.0)
    for i in range(100):
        w.record(0.05 if i < 50 else 0.001, units=1, t=500.0 + i * 0.01)
    snap = w.snapshot(window_s=4.0, t=501.0)
    assert snap["slo_violations"] == 50
    assert snap["slo_violation_rate"] == pytest.approx(0.5)
    assert snap["slo_budget_burn"] == pytest.approx(50.0)
    assert snap["slo_p99_target_ms"] == 10.0


def test_windowed_units_rate():
    w = WindowedMetrics(slot_s=1.0, n_slots=8, clock=lambda: 0.0)
    for i in range(10):
        w.record(1e-3, units=100, t=50.0 + i * 0.1)
    snap = w.snapshot(window_s=2.0, t=51.0)
    assert snap["units"] == 1000
    assert snap["units_per_s"] == pytest.approx(500.0)


def test_windowed_concurrent_recorders_lose_nothing():
    """N threads hammer one WindowedMetrics; the merged histogram must
    hold every observation (the lock contract on the hot path)."""
    w = WindowedMetrics(slot_s=60.0, n_slots=4)
    n_threads, per_thread = 8, 2_000

    def worker(seed):
        rng = np.random.default_rng(seed)
        for s in rng.uniform(1e-4, 1e-2, size=per_thread):
            w.record(float(s))

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    hist, _, _, _ = w.merged(window_s=w.max_window_s)
    assert hist.n == n_threads * per_thread


# ---------------------------------------------------------------------------
# SpanRecorder: schema round-trip, rid reconciliation, ring bound
# ---------------------------------------------------------------------------
def test_trace_schema_roundtrip_and_rid_reconciliation():
    rec = SpanRecorder(capacity=128)
    with rec.span("launch", cat="serve", kind="read", padded=512):
        pass
    rec.instant("admit", cat="admission", rid=7, kind="read", n_keys=32)
    lat = {}
    for rid in (7, 8, 9):
        t_submit = rec.t_epoch + rid * 0.010
        t_end = t_submit + 0.002 + rid * 1e-4
        rec.request(rid, kind="read", n_keys=32, t_submit=t_submit,
                    t_launch=t_submit + 0.001, t_end=t_end)
        lat[rid] = t_end - t_submit

    # full JSON round-trip — exactly what a trace viewer would parse
    trace = json.loads(json.dumps(rec.to_chrome()))
    assert trace["otherData"]["dropped_spans"] == 0
    evs = trace["traceEvents"]
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in evs)
    cats = {e.get("cat") for e in evs if e["ph"] != "M"}
    assert {"serve", "admission", "request"} <= cats
    for e in evs:
        assert e["pid"] == 0
        if e["ph"] == "X":
            assert e["dur"] >= 0 and "ts" in e
        if e["ph"] == "i":
            assert e["s"] == "t"

    # rid -> latency parsed back from the µs export matches what went in
    got = SpanRecorder.request_latencies_s(trace)
    assert set(got) == {7, 8, 9}
    for rid, s in lat.items():
        assert got[rid] == pytest.approx(s, abs=1e-8)
    # the queue/exec decomposition sums to the span duration
    for e in SpanRecorder.request_events(trace):
        a = e["args"]
        assert a["queue_us"] + a["exec_us"] == pytest.approx(e["dur"],
                                                             abs=1e-2)


def test_trace_ring_bound_reports_drops():
    rec = SpanRecorder(capacity=8)
    for i in range(20):
        rec.instant("tick", cat="serve", i=i)
    assert len(rec) == 8
    assert rec.n_dropped == 12
    trace = rec.to_chrome()
    assert trace["otherData"]["dropped_spans"] == 12
    assert trace["otherData"]["recorded_spans"] == 20
    # oldest dropped, newest kept
    kept = [e["args"]["i"] for e in trace["traceEvents"] if e["ph"] == "i"]
    assert kept == list(range(12, 20))


def test_trace_concurrent_recording_counts_every_span():
    rec = SpanRecorder(capacity=100_000)
    n_threads, per_thread = 8, 2_000

    def worker(k):
        for i in range(per_thread):
            with rec.span("w", cat="serve", k=k, i=i):
                pass

    ts = [threading.Thread(target=worker, args=(k,)) for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert rec.n_recorded == n_threads * per_thread
    assert len(rec) == n_threads * per_thread
    # every tid that recorded a span has a thread_name metadata event
    # (the OS may recycle thread idents, so distinct-count can be < N)
    trace = rec.to_chrome()
    meta_tids = {e["tid"] for e in trace["traceEvents"] if e["ph"] == "M"}
    span_tids = {e["tid"] for e in trace["traceEvents"] if e["ph"] != "M"}
    assert span_tids <= meta_tids


def test_maybe_span_none_is_noop():
    with maybe_span(None, "anything", cat="serve", x=1):
        pass
    rec = SpanRecorder()
    with maybe_span(rec, "real", cat="lifecycle"):
        pass
    assert len(rec) == 1 and rec.spans()[0].cat == "lifecycle"


# ---------------------------------------------------------------------------
# ServiceMetrics satellites: insert-only window, per-request recording
# ---------------------------------------------------------------------------
def test_insert_only_snapshot_has_live_window_and_rate():
    """Regression (satellite 2): insert-only traffic used to read as a
    zero window — lookups_per_s guarded on n_batches — so an all-write
    service reported nothing."""
    m = ServiceMetrics()
    m.observe_insert_batch(n_keys=500, admitted=480, t_start=10.0,
                           t_end=10.5)
    m.observe_insert_batch(n_keys=500, admitted=490, t_start=11.0,
                           t_end=12.0)
    snap = m.snapshot()
    assert snap["insert_keys"] == 1000
    assert snap["inserts_per_s"] == pytest.approx(1000 / 2.0)
    assert snap["lookups_per_s"] == 0.0     # no reads: rate 0, not NaN
    assert snap["mean_insert_ms"] > 0.0


def test_observe_batch_per_request_matches_trace_semantics():
    """per_request recording puts the same (t_submit, t_end) pairs into
    the histogram that `SpanRecorder.request` gets — so a trace-derived
    p99 and the snapshot p99 are the same distribution by construction."""
    m = ServiceMetrics()
    rec = SpanRecorder()
    t_end = 100.0
    per_request = []
    for rid in range(200):
        t_submit = t_end - (0.001 + rid * 1e-4)   # spread of latencies
        per_request.append((t_submit, 32))
        rec.request(rid, kind="read", n_keys=32, t_submit=t_submit,
                    t_launch=t_submit + 1e-4, t_end=t_end)
    m.observe_batch(n_keys=200 * 32, padded=8192, n_requests=200,
                    t_oldest_submit=per_request[-1][0], t_start=t_end - 1e-3,
                    t_end=t_end, per_request=per_request)
    lats = np.asarray(sorted(
        SpanRecorder.request_latencies_s(rec.to_chrome()).values()))
    trace_p99 = float(np.quantile(lats, 0.99, method="higher"))
    h = m.request_latency
    assert abs(h.bucket_index(trace_p99)
               - h.bucket_index(m.snapshot()["p99_request_ms"] / 1e3)) <= 1
    assert h.n == 200                        # one record per request
    # windowed ring saw the same per-request units (read at the same
    # synthetic completion time the observations were stamped with)
    _, units, _, _ = m.windows.merged(m.windows.max_window_s, t=t_end)
    assert units == 200 * 32


# ---------------------------------------------------------------------------
# exporters: Prometheus text, HTTP endpoints, JSONL
# ---------------------------------------------------------------------------
class _FakeProvider:
    def __init__(self, with_recorder=True):
        import time

        self.metrics = ServiceMetrics(slo_p99_ms=10.0)
        # real-clock timestamps: the windowed read uses perf_counter
        # "now", so observations must land inside the trailing window
        now = time.perf_counter()
        self.metrics.observe_batch(
            n_keys=64, padded=128, n_requests=2,
            t_oldest_submit=now - 2e-3, t_start=now - 1e-3, t_end=now,
            per_request=[(now - 2e-3, 32), (now - 1.5e-3, 32)])
        self.recorder = SpanRecorder() if with_recorder else None
        if self.recorder is not None:   # empty recorder is len()==0 falsy
            self.recorder.instant("admit", cat="admission", rid=0)


def test_prometheus_text_format():
    text = prometheus_text({"p99_ms": 1.5, "n": 3, "name": "rmi",
                            "ok": True}, labels={"ds": "amzn"})
    lines = text.strip().splitlines()
    assert "# TYPE repro_lookup_p99_ms gauge" in lines
    assert 'repro_lookup_p99_ms{ds="amzn"} 1.5' in lines
    assert 'repro_lookup_ok{ds="amzn"} 1' in lines
    assert not any("name" in ln and "rmi" in ln for ln in lines)  # non-numeric


def test_metrics_payload_contract():
    p = metrics_payload(_FakeProvider(), window_s=60.0)
    assert p["lifetime"]["requests"] == 2
    assert p["windowed"]["n"] == 2
    assert p["trace_spans"] == 1 and p["trace_dropped"] == 0


def test_metrics_server_endpoints():
    prov = _FakeProvider()
    with MetricsServer(prov, port=0) as srv:
        base = f"http://127.0.0.1:{srv.port}"

        def get(path):
            with urllib.request.urlopen(base + path, timeout=10) as r:
                return r.status, r.read().decode()

        status, text = get("/metrics")
        assert status == 200
        assert "repro_lookup_p99_request_ms" in text
        assert "repro_lookup_window_p99_ms" in text     # windowed block

        status, body = get("/metrics.json?window_s=120")
        doc = json.loads(body)
        assert status == 200 and doc["lifetime"]["lookups"] == 64

        status, body = get("/trace.json")
        assert status == 200
        assert json.loads(body)["otherData"]["dropped_spans"] == 0

        status, body = get("/healthz")
        assert status == 200 and body == "ok\n"
        with pytest.raises(urllib.error.HTTPError) as ei:
            get("/nope")
        assert ei.value.code == 404


def test_metrics_server_trace_404_when_disabled():
    with MetricsServer(_FakeProvider(with_recorder=False), port=0) as srv:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/trace.json", timeout=10)
        assert ei.value.code == 404


def test_jsonl_logger_appends_parseable_lines(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    logger = JsonlMetricsLogger(_FakeProvider(), path, interval_s=60.0)
    logger.write_once()
    logger.write_once()
    with open(path) as f:
        docs = [json.loads(ln) for ln in f]
    assert len(docs) == 2 == logger.n_written
    assert all(d["lifetime"]["requests"] == 2 for d in docs)
    # start/stop writes the final snapshot even if the interval never fired
    with JsonlMetricsLogger(_FakeProvider(), path, interval_s=60.0):
        pass
    with open(path) as f:
        assert len(f.readlines()) == 3


# ---------------------------------------------------------------------------
# end-to-end: a traced LookupService reconciles trace vs histogram
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("executor", ["sync", "async"])
def test_traced_service_reconciles_p99_and_ids(executor):
    """Serve real traffic with tracing on: every submitted request id
    appears exactly once as a request span, and the span-derived p99
    lands within one histogram bucket of the metrics-snapshot p99 (the
    §14 acceptance bound — same requests, two recording paths)."""
    from repro.data import sosd
    from repro.serve.lookup import LookupService, LookupServiceConfig

    keys = sosd.generate("amzn", 30_000, seed=3)
    q = sosd.make_queries(keys, 3_200, seed=5)
    svc = LookupService(keys, LookupServiceConfig(
        index="rmi", hyper=dict(branching=512), max_batch=256,
        deadline_ms=1.0, executor=executor, trace=True, slo_p99_ms=5000.0))
    with svc:
        futs = [svc.submit(q[i:i + 64]) for i in range(0, len(q), 64)]
        for f in futs:
            f.result(timeout=60.0)

    trace = json.loads(json.dumps(svc.recorder.to_chrome()))
    lat = SpanRecorder.request_latencies_s(trace)
    assert len(lat) == len(futs)            # one span per request, by rid
    # the request spans close out exactly the rids admission handed out
    assert set(lat) == {f.rid for f in futs}
    snap = svc.metrics.snapshot()
    trace_p99 = float(np.quantile(np.asarray(sorted(lat.values())), 0.99,
                                  method="higher"))
    h = svc.metrics.request_latency
    assert h.n == len(futs)
    assert abs(h.bucket_index(trace_p99)
               - h.bucket_index(snap["p99_request_ms"] / 1e3)) <= 1
    # the windowed surface saw the same traffic (full-history window)
    w = svc.metrics.windowed(window_s=svc.metrics.windows.max_window_s)
    assert w["lookups"] == len(q)
    # a target generous vs the first batch's compile (the sync path pays
    # first-touch lowering of the instrumented executable in-band, §15)
    # burns nothing
    assert w["slo_violations"] == 0
    # serve-side spans exist for the executor that ran; an accepted
    # request records no admission instant (its request span starts at
    # the same t_submit)
    cats = {e.get("cat") for e in trace["traceEvents"] if e["ph"] != "M"}
    assert "serve" in cats and "admission" not in cats


def test_traced_mutable_service_records_insert_and_compaction_spans():
    from repro.data import sosd
    from repro.serve.lookup.mutable_service import (
        MutableLookupService, MutableLookupServiceConfig)

    keys = sosd.generate("wiki", 20_000, seed=9)
    svc = MutableLookupService(keys, MutableLookupServiceConfig(
        index="rmi", hyper=dict(branching=256), max_batch=512,
        deadline_ms=1.0, compact_threshold=1_000, auto_compact=False,
        trace=True))
    new_keys = (np.asarray(keys[:1500], dtype=np.uint64) + 1).astype(
        np.uint64)
    with svc:
        svc.insert(new_keys).result(timeout=60.0)
        svc.submit(keys[:64]).result(timeout=60.0)
        svc.force_compact()

    spans = svc.recorder.spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    req_kinds = {s.args["kind"] for s in by_name["request"]}
    assert {"insert", "read"} <= req_kinds
    assert "compaction" in by_name          # lifecycle span, cat check:
    assert by_name["compaction"][0].cat == "lifecycle"
    assert "index_build" in by_name         # the compaction's rebuild
    assert "publish" in by_name             # ...and its hot-swap


# ---------------------------------------------------------------------------
# profiler annotations: the recorder's spans in the profiler's own trace
# ---------------------------------------------------------------------------
def _serve_under_profiler(tmp_path, trace):
    import jax

    from perfbench import scopetrace
    from repro.data import sosd
    from repro.serve.lookup import LookupService, LookupServiceConfig

    keys = sosd.generate("amzn", 20_000, seed=3)
    q = sosd.make_queries(keys, 1_024, seed=5)
    svc = LookupService(keys, LookupServiceConfig(
        index="pgm", hyper=dict(eps=32), max_batch=256, deadline_ms=1.0,
        executor="async", trace=trace))
    with svc:
        jax.profiler.start_trace(str(tmp_path))
        try:
            futs = [svc.submit(q[i:i + 128]) for i in range(0, len(q), 128)]
            for f in futs:
                f.result(timeout=60.0)
        finally:
            jax.profiler.stop_trace()
    return svc, scopetrace.read(str(tmp_path))


def test_profiler_trace_holds_lookup_annotations(tmp_path):
    """Under an active profiler, the traced service's dispatch and
    completion spans appear as `lookup.*` host events carrying the same
    `batch` numbers as the recorder's spans."""
    svc, raw = _serve_under_profiler(tmp_path, trace=True)
    ann = {}
    for name, _, dur, args in raw["lookup"]:
        assert dur >= 0
        ann.setdefault(name, set()).add(int(args["batch"]))
    ring = {}
    for s in svc.recorder.spans():
        if s.name in ("pin", "launch", "finalize"):
            ring.setdefault("lookup." + s.name, set()).add(s.args["batch"])
    for name in ("lookup.pin", "lookup.launch", "lookup.finalize"):
        assert ann.get(name), name
        assert ann[name] <= ring[name]
    # the request span is recorded after the fact: ring only
    assert "lookup.request" not in ann


def test_untraced_service_writes_no_annotation(tmp_path):
    svc, raw = _serve_under_profiler(tmp_path, trace=False)
    assert svc.recorder is None
    assert raw["lookup"] == []


def test_profiler_trace_names_the_served_plan_stages(tmp_path):
    """The trace's own copy of the served program's HLO gives each of
    its instructions a scope path that names the plan stage."""
    import glob

    from perfbench import scopetrace

    _serve_under_profiler(tmp_path, trace=False)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    with open(path, "rb") as f:
        names = scopetrace.op_names(f.read())
    scopes = {scope for module, table in names.items()
              if module.startswith("jit_run_instr(")
              for scope in table.values()}
    for stage in ("predict", "last_mile", "health_stats"):
        assert any(scopetrace.under(s, stage) for s in scopes), stage


# ---------------------------------------------------------------------------
# named plan stages: HLO metadata only
# ---------------------------------------------------------------------------
def _compiled_text(fn, *args):
    """Compiled HLO without metadata or debug sections."""
    import re

    text = fn.lower(*args).compile().as_text().split("\nFileNames")[0]
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return re.sub(r"stack_frame_id=\d+", "", text)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("index,hyper", [
    ("pgm", dict(eps=32)), ("radix_spline", dict(eps=32, radix_bits=12))])
def test_instrumented_program_names_its_stages(index, hyper, backend,
                                               monkeypatch):
    """The lowered instrumented program carries the `predict`,
    `last_mile` and `health_stats` scopes; without them it compiles to
    the same instructions and gives bit-identical answers."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from repro.core import plan as plan_mod
    from repro.core import spec as spec_mod
    from repro.data import sosd

    keys = sosd.generate("amzn", 20_000, seed=3)
    q = jnp.asarray(sosd.make_queries(keys, 512, seed=5))
    build = spec_mod.build(spec_mod.IndexSpec(
        index, hyper, backend=backend).validated(), keys)
    plan = plan_mod.lower(build, jnp.asarray(keys))
    ops = plan.operands(backend)
    args = (q, np.int32(q.size), ops)

    def program():
        return jax.jit(plan._program_expr("instr", backend, None, False,
                                          None, None))

    scoped = program()
    text = scoped.lower(*args).as_text(debug_info=True)
    for stage in ("predict", "last_mile", "health_stats"):
        assert f"/{stage}/" in text, stage
    pos, stats = scoped(*args)
    np.testing.assert_array_equal(
        np.asarray(pos), np.searchsorted(keys, np.asarray(q), side="left"))

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = program()
    assert "/predict/" not in plain.lower(*args).as_text(debug_info=True)
    assert _compiled_text(plain, *args) == _compiled_text(scoped, *args)
    pos0, stats0 = plain(*args)
    np.testing.assert_array_equal(np.asarray(pos0), np.asarray(pos))
    np.testing.assert_array_equal(np.asarray(stats0), np.asarray(stats))
