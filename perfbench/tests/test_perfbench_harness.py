"""CPU rehearsal of the harness: a whole run at tiny size, with the look
for a chip stubbed, through configuration, traffic and metric lookup by
name (Pallas in interpret mode)."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import harness

HERE = Path(harness.__file__).resolve().parent
N_KEYS = 20_000
SECONDS = 0.6


ROUTED = "books-pgm.probe1024-routed"
ROUTED_CONFIG = "books-pgm-routed4x2"


def _add_routed_cell(spec: dict, root: Path):
    """A routed cell to rehearse the harness's topology on, since no
    cell of BENCHMARK.json is routed: the books configuration in 4
    range shards x 2 replicas under the probe mix, reporting what the
    probe cell does (but the whole-program readers, whose busy time is
    averaged over chips) and the routed readers besides."""
    base = next(c for c in spec["configs"]
                if c["name"] == "sosd-books-200m-pgm")
    cfg = json.loads((root / base["file"]).read_text())
    cfg["service"].update(shards=4, replicas=2)
    file = f"configs/{ROUTED_CONFIG}.json"
    (root / file).write_text(json.dumps(cfg))
    spec["configs"].append(dict(base, name=ROUTED_CONFIG, file=file))
    spec["workloads"].append({"name": ROUTED, "config": ROUTED_CONFIG,
                              "traffic": "probe1024", "chips": 4,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if ("books-pgm.probe1024" in m.get("workloads", ())
                and not m["name"].startswith("program_")):
            m["workloads"].append(ROUTED)
    spec["per_layer"] += [
        {"name": "route_ms.probe", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "executor and dispatch",
         "moves": "lookups_per_s", "workloads": [ROUTED]},
        {"name": "busy_imbalance.probe", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device",
         "moves": "lookups_per_s", "workloads": [ROUTED]}]


def _tiny_bench(root: Path, extra_cells=(), extra_per_layer=()):
    """BENCHMARK.json with the real cells pointed at tiny copies of
    their configuration and traffic files, and a routed cell added."""
    spec = json.loads((harness.CHECKOUT / "BENCHMARK.json").read_text())
    (root / "configs").mkdir(exist_ok=True)
    (root / "traffic").mkdir(exist_ok=True)
    for c in spec["configs"]:
        cfg = json.loads((harness.CHECKOUT / c["file"]).read_text())
        cfg["keys"]["n"] = N_KEYS
        c["file"] = f"configs/{c['name']}.json"
        (root / c["file"]).write_text(json.dumps(cfg))
    for w in spec["workloads"]:
        t = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
        if t["loop"] == "closed":
            t.update(callers=4, keys_per_request=256, pool_requests=32)
        else:
            t.update(rate_per_s=400)
        (root / "traffic" / f"{w['traffic']}.json").write_text(json.dumps(t))
    _add_routed_cell(spec, root)
    spec["workloads"] += list(extra_cells)
    spec["per_layer"] += list(extra_per_layer)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Bench(root=root, dirs=(root, HERE))


@pytest.fixture
def on_cpu(monkeypatch):
    import jax

    monkeypatch.setattr(harness, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "_enable_compile_cache", lambda: None)


def _run(bench, cell, trace=False, seed=2**31 + 99):
    return harness.run_cell(bench, cell, seed, SECONDS, trace, 0.0)


def _assert_line_shape(result, bench, cell, section):
    assert list(result)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    json.loads(json.dumps(result))                   # one JSON line
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    names = {m["name"] for m in bench.metrics(section, cell)}
    assert set(result["metrics"]) <= names
    for v in result["metrics"].values():
        assert set(v) == {"value", "unit"}
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.fixture
def built(monkeypatch):
    """The services `run_cell` builds, kept for a look once it is done."""
    out = []
    build = harness.build_service

    def keep(*args, **kw):
        out.append(build(*args, **kw))
        return out[-1]

    monkeypatch.setattr(harness, "build_service", keep)
    return out


@pytest.mark.parametrize("cell", ["books-pgm.probe1024", "osm-rs.probe1024",
                                  "books-pgm.get-zipf", ROUTED])
def test_cell_runs_end_to_end(tmp_path, on_cpu, built, cell):
    from repro.serve.lookup.dispatch import RoutedDispatcher
    from repro.serve.lookup.registry import RoutedGeneration

    bench = _tiny_bench(tmp_path)
    r = _run(bench, cell)
    _assert_line_shape(r, bench, cell, "end_to_end")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    want = {m["name"] for m in bench.metrics("end_to_end", cell)}
    assert set(r["metrics"]) == want
    (svc,) = built
    routed = bench.config(bench.workload(cell)["config"])["service"].get(
        "shards", 1) > 1
    assert isinstance(svc.dispatcher, RoutedDispatcher) == routed
    assert isinstance(svc.generation, RoutedGeneration) == routed
    if routed:
        # on one CPU device the 4 x 2 lanes wrap onto it
        assert svc.dispatcher.n_shards == 4
        assert svc.dispatcher.replicas == (2, 2, 2, 2)
        assert len(svc.generation.shards) == 4


def test_traced_run_reports_per_layer_metrics(tmp_path, on_cpu):
    bench = _tiny_bench(tmp_path)
    r = _run(bench, "books-pgm.probe1024", trace=True)
    _assert_line_shape(r, bench, "books-pgm.probe1024", "per_layer")
    assert r["correct"]
    # spans are read on the CPU; the device metrics need a TPU trace
    assert {"build_s", "dispatch_ms.probe"} <= set(r["metrics"])
    assert "bounded_search_roofline" not in r["metrics"]
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_traced_routed_run_reports_route_ms(tmp_path, on_cpu):
    bench = _tiny_bench(tmp_path)
    r = _run(bench, ROUTED, trace=True)
    _assert_line_shape(r, bench, ROUTED, "per_layer")
    assert r["correct"]
    assert {"build_s", "dispatch_ms.probe", "pin_ms.probe",
            "route_ms.probe"} <= set(r["metrics"])
    assert r["metrics"]["route_ms.probe"]["value"] > 0
    assert r["metrics"]["route_ms.probe"]["unit"] == "ms"
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def _config_before_topology(cfg, keys_per_request, trace):
    """The `LookupServiceConfig` the harness built before a configuration
    could state a topology."""
    from repro.core.spec import IndexSpec
    from repro.serve.lookup import LookupServiceConfig

    s = cfg["service"]
    return LookupServiceConfig(
        spec=IndexSpec(cfg["index"]["name"], dict(cfg["index"]["hyper"]),
                       backend=s["backend"]),
        executor=s["executor"], max_batch=int(s["max_batch"]),
        deadline_ms=float(s["deadline_ms"]), health=bool(s["health"]),
        slots=int(s["slots"]),
        warm_buckets=harness._warm_buckets(int(s["max_batch"]),
                                           keys_per_request),
        trace=trace, trace_capacity=harness.TRACE_SPANS)


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", ["books-pgm.probe1024", "osm-rs.probe1024",
                                  "books-pgm.get-zipf"])
def test_broadcast_cells_build_the_config_they_built_before(cell, trace):
    bench = harness.Bench()
    cfg = bench.config(bench.workload(cell)["config"])
    k = int(bench.traffic(bench.workload(cell)["traffic"])
            ["keys_per_request"])
    got = harness.service_config(cfg, k, trace)
    assert got == _config_before_topology(cfg, k, trace)
    assert (got.shards, got.replicas, got.topology) == (1, 1, None)


def test_routed_config_states_its_topology_and_leaves_the_buckets(tmp_path):
    bench = _tiny_bench(tmp_path)
    cfg = bench.config(bench.workload(ROUTED)["config"])
    got = harness.service_config(cfg, 1024, False)
    assert (got.shards, got.replicas, got.warm_buckets) == (4, 2, ())
    assert got == dataclasses.replace(
        _config_before_topology(cfg, 1024, False), shards=4, replicas=2,
        warm_buckets=())


def test_routed_build_numbers_take_the_largest_error_of_the_shards():
    from repro.serve.lookup.registry import RoutedGeneration

    metas = [{"max_err": 150, "levels": 2, "segments": 40, "name": "pgm"},
             {"max_err": 368, "levels": 2, "segments": 51},
             {"max_err": 152, "levels": 3, "segments": 47},
             {"max_err": 174, "levels": 2, "segments": 44}]
    gen = RoutedGeneration(
        version=1, topology=None,
        shards=tuple(SimpleNamespace(build=SimpleNamespace(meta=m))
                     for m in metas))
    assert harness.build_numbers(gen) == {"max_err": 368, "levels": 3,
                                          "segments": 40}
    one = SimpleNamespace(build=SimpleNamespace(meta=metas[1]))
    assert harness.build_numbers(one) == {"max_err": 368, "levels": 2,
                                          "segments": 51}


def test_routed_set_up_warms_every_lane_bucket(tmp_path, on_cpu):
    """Sub-batches of every size a lane can be given, each sent to one
    shard, run with no compile after set-up, and answer exactly."""
    bench = _tiny_bench(tmp_path)
    st = harness.set_up(bench, ROUTED, 2**31 + 5, False)
    svc = st.service
    try:
        gen = svc.generation
        assert st.build["max_err"] == max(s.build.meta["max_err"]
                                          for s in gen.shards)
        # 4 shards x 2 replicas, each lane warmed at every bucket from
        # 128 to 2048; the sizes below pad to each of them
        assert len(svc.exec_cache) == 8 * 5
        misses = svc.exec_cache.counters()[1]
        offs = gen.topology.offsets
        rng = np.random.default_rng(5)
        for size in (1, 100, 200, 400, 900, 2000):
            s = int(rng.integers(4))
            q = st.keys[rng.integers(offs[s], offs[s + 1], size)]
            got = svc.submit(q).result(60)
            np.testing.assert_array_equal(
                got, np.searchsorted(st.keys, q, side="left"))
        assert svc.exec_cache.counters()[1] == misses
    finally:
        svc.stop()


def test_new_traffic_and_metric_files_are_found_by_name(tmp_path, on_cpu):
    """A later change adds a mix and a metric as files and entries only."""
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"loop": "closed", "callers": 2, "keys_per_request": 128,
         "present_frac": 0.5, "pool_requests": 8}))
    (tmp_path / "metrics" / "dummy_metric.py").write_text(
        "def read(run):\n    return float(run.keys_per_request)\n")
    cell = {"name": "books-pgm.dummy", "config": "sosd-books-200m-pgm",
            "traffic": "dummy-mix", "chips": 1, "why": "test"}
    metric = {"name": "dummy_metric", "unit": "keys", "better": "higher",
              "source": "program_counter", "layer": "admission",
              "moves": "setup_s", "workloads": ["books-pgm.dummy"]}
    bench = _tiny_bench(tmp_path, [cell], [metric])
    r = _run(bench, "books-pgm.dummy", trace=True)
    assert r["correct"]
    assert r["metrics"]["dummy_metric"] == {"value": 128.0, "unit": "keys"}


def _alter_first(pos):
    pos[0] += 1


def _drop_half(pos):
    half = pos.size // 2
    pos[half:] = pos[:pos.size - half]


@pytest.mark.parametrize("fault", [_alter_first, _drop_half],
                         ids=["answer_altered", "half_batch_left_out"])
def test_faults_in_the_timed_path_make_correct_false(tmp_path, on_cpu,
                                                      monkeypatch, fault):
    from repro.serve.lookup.dispatch import ShardedDispatcher

    finalize = ShardedDispatcher.finalize

    def broken(out, m, instrumented=False):
        res = finalize(out, m, instrumented=instrumented)
        pos, stats = res if instrumented else (res, None)
        pos = np.array(pos)
        fault(pos)
        return (pos, stats) if instrumented else pos

    monkeypatch.setattr(ShardedDispatcher, "finalize", staticmethod(broken))
    bench = _tiny_bench(tmp_path)
    r = _run(bench, "books-pgm.probe1024")
    assert not r["correct"]
    assert r["checks"]["wrong_answers"]["value"] > 0
    assert r["failed"] > 0


def _lift_left_out(handle_cls):
    """The exchange between chips left out: each shard's local ranks come
    back without the offset of its range."""
    finalize = handle_cls.finalize

    def broken(self):
        self.rctx = dataclasses.replace(
            self.rctx, offsets=(0,) * len(self.rctx.offsets))
        return finalize(self)
    return broken


def _shard_left_out(handle_cls):
    """One shard's sub-batch left out of the gather."""
    finalize = handle_cls.finalize

    def broken(self):
        pos, stats, padded = finalize(self)
        starts = np.concatenate([[0], np.cumsum(self.counts)])
        s = int(np.argmax(self.counts))
        pos[self.order[starts[s]:starts[s + 1]]] = 0
        return pos, stats, padded
    return broken


def _routed_lane_fault(fault):
    def broken(handle_cls):
        finalize = handle_cls.finalize

        def lane_finalize(self):
            pos, stats, padded = finalize(self)
            fault(pos)
            return pos, stats, padded
        return lane_finalize
    return broken


@pytest.mark.parametrize("fault", [_routed_lane_fault(_alter_first),
                                   _routed_lane_fault(_drop_half),
                                   _lift_left_out, _shard_left_out],
                         ids=["answer_altered", "half_batch_left_out",
                              "shard_offset_left_out", "shard_left_out"])
def test_faults_in_the_routed_path_make_correct_false(tmp_path, on_cpu,
                                                       monkeypatch, fault):
    from repro.serve.lookup import dispatch

    monkeypatch.setattr(dispatch._RoutedHandle, "finalize",
                        fault(dispatch._RoutedHandle))
    bench = _tiny_bench(tmp_path)
    r = _run(bench, ROUTED)
    assert not r["correct"]
    assert r["checks"]["wrong_answers"]["value"] > 0
    assert r["failed"] > 0


def _command(cwd: Path, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "books-pgm.probe1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_exits_non_zero_without_a_tpu():
    p = _command(harness.CHECKOUT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_command_exits_non_zero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(harness.CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
