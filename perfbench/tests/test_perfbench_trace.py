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
