"""CPU rehearsal of the harness: a whole run at tiny size, with the look
for a chip stubbed, through configuration, traffic and metric lookup by
name (Pallas in interpret mode)."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from perfbench import harness

HERE = Path(harness.__file__).resolve().parent
N_KEYS = 20_000
SECONDS = 0.6


def _tiny_bench(root: Path, extra_cells=(), extra_per_layer=()):
    """BENCHMARK.json with the real cells pointed at tiny copies of
    their configuration and traffic files."""
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


@pytest.mark.parametrize("cell", ["books-pgm.probe1024", "osm-rs.probe1024",
                                  "books-pgm.get-zipf"])
def test_cell_runs_end_to_end(tmp_path, on_cpu, cell):
    bench = _tiny_bench(tmp_path)
    r = _run(bench, cell)
    _assert_line_shape(r, bench, cell, "end_to_end")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    want = {m["name"] for m in bench.metrics("end_to_end", cell)}
    assert set(r["metrics"]) == want


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
