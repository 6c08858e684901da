"""Trace reduction and the roofline's byte count."""
import importlib.util
import json
from pathlib import Path

import pytest

from perfbench import roofline, tracefile

OPS = [("fusion.1", 0.0, 10.0), ("bounded_search", 5.0, 10.0),
       ("copy", 30.0, 5.0), ("bounded_search", 40.0, 20.0)]


def test_busy_time_is_the_union_of_overlapping_operations():
    assert tracefile.merged(OPS, 0.0, 100.0) == [(0.0, 15.0), (30.0, 35.0),
                                                  (40.0, 60.0)]
    assert tracefile.busy_ns(OPS, 0.0, 100.0) == 40.0


def test_busy_time_is_clipped_to_the_window():
    assert tracefile.busy_ns(OPS, 8.0, 50.0) == 7.0 + 5.0 + 10.0


def test_idle_gaps_fill_the_rest_of_the_window():
    gaps = tracefile.gaps(OPS, 0.0, 100.0)
    assert gaps == [(15.0, 30.0), (35.0, 40.0), (60.0, 100.0)]
    assert sum(b - a for a, b in gaps) + tracefile.busy_ns(OPS, 0, 100) \
        == 100.0


def test_kernel_time_sums_the_kernel_operations_by_name():
    assert tracefile.op_ns(OPS, "bounded_search", 0.0, 100.0) == 30.0
    assert tracefile.op_ns(OPS, "bounded_search", 0.0, 50.0) == 20.0


def test_top_ops_in_seconds_by_total_time():
    top = tracefile.top_ops(OPS, 0.0, 100.0, k=2)
    assert top == [("bounded_search", 30e-9), ("fusion.1", 10e-9)]


def test_overlap_of_disjoint_intervals():
    assert tracefile.overlap([(0, 5), (10, 20)], 3, 12) == 4


def test_roofline_bytes_come_from_the_error_bound_and_the_model():
    build = {"max_err": 154, "levels": 3}
    assert roofline.search_bytes(build) == 8 + 8 * 154 + 8
    cfg = {"model_bytes_per_level": 24}
    assert roofline.lookup_bytes(cfg, build) == 8 + 8 * 154 + 8 + 72


# -- a small trace recorded on the chip: two served probe batches --------
RECORDED = json.loads((Path(__file__).parent / "data" /
                       "v5e_probe_trace.json").read_text())


def _recorded():
    ops = [tuple(e) for e in RECORDED["ops"]]
    t0, t1 = RECORDED["window_ns"]
    return ops, t0, t1


def test_recorded_busy_and_idle_shares():
    ops, t0, t1 = _recorded()
    busy = tracefile.busy_ns(ops, t0, t1)
    assert 0 < busy <= t1 - t0
    idle = sum(b - a for a, b in tracefile.gaps(ops, t0, t1))
    assert idle + busy == pytest.approx(t1 - t0)


def test_recorded_kernel_time_counts_the_kernel_not_its_readers():
    ops, t0, t1 = _recorded()
    own = [d for n, s, d in ops if n.startswith("%bounded_search")]
    readers = [n for n in (o[0] for o in ops)
               if "bounded_search" in n and not n.startswith("%bounded")]
    assert len(own) == 2 and readers
    assert tracefile.op_ns(ops, "bounded_search", t0, t1) == sum(own)


def test_recorded_top_op_is_the_whole_array_key_split():
    ops, t0, t1 = _recorded()
    name, seconds = tracefile.top_ops(ops, t0, t1, k=1)[0]
    assert name.endswith("u32[200000000] X64SplitHigh") or \
        name.endswith("u32[200000000] X64SplitLow")
    assert seconds > 0


def test_recorded_roofline_share_is_a_share():
    from types import SimpleNamespace

    from perfbench import harness

    spec = importlib.util.spec_from_file_location(
        "bsr", Path(harness.HERE) / "metrics" / "bounded_search_roofline.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    ops, t0, t1 = _recorded()
    run = SimpleNamespace(
        trace={"devices": {"/device:TPU:0": ops}, "t0": t0, "t1": t1},
        answered_in_window=2 * 2048, build={"max_err": 150},
        peaks=lambda: harness.peaks("TPU v5 lite"))
    share = reader.read(run)
    assert 0 < share < 100
    ns = tracefile.op_ns(ops, "bounded_search", t0, t1)
    assert share == pytest.approx(
        100 * 4096 * (8 + 8 * 150 + 8) / 819e9 * 1e9 / ns)


def test_unknown_device_kind_is_an_error():
    from perfbench import harness

    with pytest.raises(KeyError):
        harness.peaks("cpu")


# -- busy_imbalance.probe: the busiest chip over the mean chip ------------
def _reader(name):
    from perfbench import harness

    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), Path(harness.HERE) / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _four_chips(busy, chips=4):
    """A run whose traced devices are busy ``busy[i]`` ns of a 100 ns
    window, as two operations each (one overlapping the other, one
    reaching past the window's end)."""
    from types import SimpleNamespace

    devices = {f"/device:TPU:{i}": [("fusion", 0.0, b / 2),
                                    ("bounded_search", b / 4, b / 4),
                                    ("copy", 100.0 - b / 2, b)]
               for i, b in enumerate(busy)}
    return SimpleNamespace(trace={"devices": devices, "t0": 0.0,
                                  "t1": 100.0},
                           cell={"chips": chips})


def test_busy_imbalance_is_the_busiest_chip_over_the_mean():
    read = _reader("busy_imbalance.probe").read
    assert read(_four_chips([40.0, 40.0, 40.0, 40.0])) == pytest.approx(100)
    assert read(_four_chips([20.0, 20.0, 40.0, 40.0])) == pytest.approx(
        100 * 40 / 30)
    assert read(_four_chips([10.0, 30.0, 50.0, 70.0])) == pytest.approx(
        100 * 70 / 40)


def test_busy_imbalance_counts_a_chip_idle_all_window():
    read = _reader("busy_imbalance.probe").read
    # an idle chip traced with no operation, and one not in the trace
    assert read(_four_chips([30.0, 30.0, 30.0, 0.0])) == pytest.approx(
        100 * 30 / 22.5)
    assert read(_four_chips([30.0, 30.0, 30.0])) == pytest.approx(
        100 * 30 / 22.5)
    assert read(_four_chips([0.0, 0.0, 0.0, 0.0])) is None
    untraced = _four_chips([1.0])
    untraced.trace = None
    assert read(untraced) is None


# -- the idle breakdown: a sweep, equal to the per-gap scan ---------------
def _idle_by_host_scan(trace, spans, ops):
    """`harness._idle_by_host` as it was, summing every interval's
    overlap for each gap: the reference for the sweep."""
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
    tot = {}
    for a, b in tracefile.gaps(ops, trace["t0"], trace["t1"]):
        cover = {n: tracefile.overlap(iv, a, b) for n, iv in acts.items()}
        best = max(cover, key=cover.get)
        label = best if cover[best] > 0 else "no request pending"
        tot[label] = tot.get(label, 0.0) + (b - a) / 1e9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:10]


def _random_trace(seed):
    """Operations on up to four devices and host spans of every kind the
    breakdown reads, over a window of about a millisecond, with an
    anchor offset."""
    import numpy as np

    from repro.obs.trace import Span

    rng = np.random.default_rng(seed)
    off = float(rng.uniform(-5e5, 5e5))
    t0, t1 = 1e6 + off, 2e6 + off
    ops = []
    for _ in range(int(rng.integers(1, 5))):
        n = int(rng.integers(0, 300))
        starts = np.sort(rng.uniform(0.9e6, 2.1e6, n)) + off
        ops += [("op", float(s), float(d))
                for s, d in zip(starts, rng.exponential(2e3, n))]
    spans = []
    for name in ("launch", "finalize", "compile", "request", "pin"):
        n = int(rng.integers(0, 200))
        for s, d in zip(rng.uniform(0.9e-3, 2.1e-3, n),
                        rng.exponential(5e-6, n)):
            args = ({"queue_us": float(d * 1e6)} if name == "request"
                    else {"batch": 1})
            spans.append(Span(name=name, cat="serve", t0=float(s),
                              dur=float(d), tid=1, args=args))
    return {"t0": t0, "t1": t1, "offset": off}, spans, ops


@pytest.mark.parametrize("seed", range(40))
def test_idle_sweep_equals_the_per_gap_scan_on_random_traces(seed):
    from perfbench import harness

    trace, spans, ops = _random_trace(seed)
    assert harness._idle_by_host(trace, spans, ops) == \
        _idle_by_host_scan(trace, spans, ops)


def test_idle_sweep_equals_the_per_gap_scan_on_the_recorded_trace():
    from perfbench import harness
    from repro.obs.trace import Span

    rec = json.loads((Path(__file__).parent / "data" /
                      "v5e_probe_annotated_trace.json").read_text())
    ops = [tuple(o[:3]) for o in rec["ops"]]
    spans = [Span(name=n, cat="serve", t0=s / 1e9, dur=d / 1e9, tid=1,
                  args=a) for n, s, d, a in rec["spans"]]
    t0, t1 = rec["window_ns"]
    trace = {"t0": t0, "t1": t1, "offset": 0.0}
    got = harness._idle_by_host(trace, spans, ops)
    assert {label for label, _ in got} >= {"launch", "finalize"}
    assert got == _idle_by_host_scan(trace, spans, ops)
