"""Plan stages by scope path, dispatch annotations, and the readers of
`pin_ms.*` and `device_idle_dispatch.*`."""
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench import harness, scopetrace
from repro.obs.trace import Span

OPS = [("fusion.1", 0.0, 10.0, "jit(run_instr)/jit(main)/predict/mul"),
       ("fusion.2", 10.0, 5.0, "jit(run_instr)/jit(main)/health_stats/"
                               "predict/add"),
       ("bounded_search.1", 20.0, 20.0, "jit(run_instr)/last_mile/"
                                        "shard_map/pallas_call"),
       ("reduce.3", 50.0, 4.0, "jit(run_instr)/jit(main)/health_stats/"
                               "reduce_sum"),
       ("copy.4", 60.0, 2.0, ""),
       ("fusion.5", 70.0, 3.0, "jit(predict)/mul")]
ANNOTATIONS = [("lookup.pin", 0.0, 18.0, {"batch": 1}),
               ("lookup.gather", 18.0, 1.0, {"batch": 1}),
               ("lookup.launch", 19.0, 3.0, {"batch": 1, "kind": "read"}),
               ("lookup.finalize", 22.0, 40.0, {"batch": 1})]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), harness.HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_scope_parts_not_substrings_name_a_stage():
    assert scopetrace.under("jit(f)/health_stats/predict/add", "predict")
    assert scopetrace.under("jit(f)/health_stats/predict/add",
                            "health_stats")
    assert not scopetrace.under("jit(predict)/mul", "predict")
    assert not scopetrace.under("", "predict")


def test_stage_time_by_scope_with_an_exclusion():
    assert scopetrace.stage_ns(OPS, 0, 100, "predict") == 15.0
    assert scopetrace.stage_ns(OPS, 0, 100, "predict", "health_stats") == 10
    assert scopetrace.stage_ns(OPS, 0, 100, "health_stats") == 9.0
    assert scopetrace.stage_ns(OPS, 0, 100, "last_mile") == 20.0
    # clipped to the window
    assert scopetrace.stage_ns(OPS, 5, 30, "last_mile") == 10.0
    assert scopetrace.stage_ns(OPS, 5, 30, "predict") == 10.0


def test_idle_while_the_dispatch_annotations_are_open():
    iv = scopetrace.intervals(ANNOTATIONS, scopetrace.DISPATCH)
    assert iv == [(0.0, 18.0), (18.0, 19.0), (19.0, 22.0)]
    # gaps of the ops in [0, 100]: 15-20, 40-50, 54-60, 62-70, 73-100;
    # the dispatch annotations cover [0, 22] of them: 15-20
    assert scopetrace.idle_while(OPS, iv, 0, 100) == 5.0
    assert scopetrace.idle_while(
        OPS, scopetrace.intervals(ANNOTATIONS, ["finalize"]), 0, 100) \
        == 10.0 + 6.0
    assert scopetrace.idle_while(OPS, [], 0, 100) == 0.0


def _run(spans, ops=None, offset=0.0, window=(0.0, 1.0), t=(0.0, 100.0)):
    return SimpleNamespace(
        spans=spans,
        window=SimpleNamespace(t_start=window[0], t_end=window[1]),
        window_spans=lambda name: [s for s in spans if s.name == name
                                   and window[0] <= s.t0 <= window[1]],
        trace=None if ops is None else {
            "devices": {"/device:TPU:0": [o[:3] for o in ops]},
            "t0": t[0], "t1": t[1], "offset": offset})


def _span(name, t0, dur, batch):
    return Span(name=name, cat="serve", t0=t0, dur=dur, tid=1,
                args={"batch": batch})


@pytest.mark.parametrize("cell", ["probe", "get"])
def test_pin_ms_is_the_mean_pin_span_in_the_window(cell):
    reader = _reader(f"pin_ms.{cell}")
    spans = [_span("pin", 0.1, 0.010, 1), _span("pin", 0.5, 0.012, 2),
             _span("pin", 1.5, 0.5, 3), _span("launch", 0.2, 0.001, 1)]
    assert reader.read(_run(spans)) == pytest.approx(11.0)
    assert reader.read(_run([])) is None


@pytest.mark.parametrize("cell", ["probe", "get"])
def test_device_idle_dispatch_puts_spans_on_the_profiler_clock(cell):
    reader = _reader(f"device_idle_dispatch.{cell}")
    # the annotations' intervals as program spans in seconds, shifted by
    # an anchor offset of -1000 ns
    spans = [_span(n[len("lookup."):], (s + 1000.0) / 1e9, d / 1e9,
                   a["batch"]) for n, s, d, a in ANNOTATIONS]
    run = _run(spans, OPS, offset=-1000.0)
    assert reader.read(run) == pytest.approx(5.0)      # 5 ns of 100
    # a program that records no `pin` span reports nothing
    assert reader.read(_run([s for s in spans if s.name != "pin"], OPS,
                            offset=-1000.0)) is None
    assert reader.read(_run(spans)) is None            # untraced


def test_an_operation_takes_its_scope_from_the_running_program():
    names = {"jit_run_instr(7)": {"fusion.1": "jit(run_instr)/predict/mul",
                                  "bounded_search.1": "jit(run_instr)/"
                                                      "last_mile/pallas_call"},
             "jit_dynamic_slice(3)": {"fusion.1": "jit(dynamic_slice)/x"}}
    modules = [("jit_dynamic_slice(3)", 0.0, 10.0),
               ("jit_run_instr(7)", 20.0, 30.0)]
    events = [("%fusion.1 = u32[1] fusion(%a)", 2.0, 1.0),
              ("%fusion.1 = f32[2048] fusion(%q)", 21.0, 5.0),
              ("%bounded_search.1 = s32[2048] custom-call(%a)", 30.0, 9.0),
              ("%copy.2 = u32[2048] copy(%a)", 40.0, 1.0),
              ("%fusion.1 = f32[2048] fusion(%q)", 60.0, 1.0)]
    got = [op[3] for op in scopetrace._scoped(events, modules, names)]
    assert got == ["jit(dynamic_slice)/x", "jit(run_instr)/predict/mul",
                   "jit(run_instr)/last_mile/pallas_call", "", ""]


# -- annotations recorded on the chip: two served probe batches -----------
RECORDED = json.loads((Path(__file__).parent / "data" /
                       "v5e_probe_annotated_trace.json").read_text())


def _recorded():
    ops = [tuple(o) for o in RECORDED["ops"]]
    ann = [tuple(a) for a in RECORDED["lookup"]]
    t0, t1 = RECORDED["window_ns"]
    return ops, ann, t0, t1


def _one(ann, name, batch):
    (iv,) = [(s, s + d) for n, s, d, a in ann
             if n == "lookup." + name and a["batch"] == batch]
    return iv


def _whole_array_split(op):
    return ("X64Split" in op[0]
            and op[0].partition(" = ")[2].startswith("u32[200000000]"))


def test_recorded_pin_holds_its_batch_whole_array_split():
    ops, ann, _, _ = _recorded()
    for b in RECORDED["batches"]:
        a, z = _one(ann, "pin", b)
        inside = [o for o in ops if _whole_array_split(o)
                  and a <= o[1] and o[1] + o[2] <= z]
        assert sorted(o[0].partition('custom_call_target="')[2]
                      .split('"')[0] for o in inside) == ["X64SplitHigh",
                                                          "X64SplitLow"]


def test_recorded_kernel_starts_after_its_enqueue_opens():
    ops, ann, _, _ = _recorded()
    kernels = sorted(o[1] for o in ops if o[0].startswith("%bounded_search"))
    for b in RECORDED["batches"]:
        opened, _ = _one(ann, "enqueue", b)
        first = min(k for k in kernels if k > opened)
        assert first - opened < 2e6                    # within 2 ms
        assert first > _one(ann, "launch", b)[0]


def test_recorded_pin_waits_for_the_previous_lookup_program():
    """The second batch's split queues behind the first batch's lookup
    program: the pin's time is the split, that wait and the copy."""
    ops, ann, _, _ = _recorded()
    first, second = RECORDED["batches"]
    opened, _ = _one(ann, "enqueue", first)
    kernel = min((o for o in ops if o[0].startswith("%bounded_search")
                  and o[1] > opened), key=lambda o: o[1])
    pin = _one(ann, "pin", second)
    split = min(o[1] for o in ops if _whole_array_split(o)
                and pin[0] <= o[1] <= pin[1])
    assert pin[0] < kernel[1] and kernel[1] + kernel[2] <= split


def test_recorded_readers_agree_with_the_annotations():
    ops, ann, t0, t1 = _recorded()
    spans = [Span(name=n, cat="serve", t0=s / 1e9, dur=d / 1e9, tid=1,
                  args=a) for n, s, d, a in RECORDED["spans"]]
    run = _run(spans, ops, offset=0.0, window=(t0 / 1e9, t1 / 1e9),
               t=(t0, t1))
    from_spans = _reader("device_idle_dispatch.probe").read(run)
    from_annotations = 100 * scopetrace.idle_while(
        ops, scopetrace.intervals(ann, scopetrace.DISPATCH), t0, t1) \
        / (t1 - t0)
    assert 0 < from_annotations < 100
    assert from_spans == pytest.approx(from_annotations, abs=0.5)
    pins = [d for n, _, d, _ in ann if n == "lookup.pin"]
    assert _reader("pin_ms.probe").read(run) == pytest.approx(
        sum(pins) / len(pins) / 1e6, abs=0.05)
