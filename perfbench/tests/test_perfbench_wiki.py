"""The wiki configuration at a size a test run holds: its key-set recipe,
its two readers, its cell and the get cell of the osm configuration
through the harness on the CPU (Pallas in interpret mode), and the
control of `correct` on its cell."""
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import control, fetch_bytes, harness, keygen
from perfbench.keysets import wiki
from perfbench.tests.test_perfbench_control import DENSE
from perfbench.tests.test_perfbench_harness import (_assert_line_shape,
                                                    _tiny_bench)
from perfbench.tests.test_perfbench_harness import on_cpu  # noqa: F401

N = 20_000
BIG_SEED = 2**31 + 12345           # seeds run past 32 signed bits
WIKI = "wiki-rmi.probe1024"
OSM_GET = "osm-rs.get-zipf"


# -- the key-set recipe --------------------------------------------------
def test_wiki_keys_are_exactly_n_sorted_and_unique():
    keys = wiki.generate(N, BIG_SEED)
    assert keys.dtype == np.uint64 and keys.shape == (N,)
    assert np.all(keys[1:] > keys[:-1])
    assert int(keys[0]) >= wiki.T0_MS


def test_wiki_same_seed_same_keys_other_seed_other_keys():
    a = wiki.generate(N, 7)
    assert np.array_equal(a, wiki.generate(N, 7))
    assert not np.array_equal(a, wiki.generate(N, 8))


def _draw(i, size, seed=BIG_SEED):
    import jax

    jax.config.update("jax_enable_x64", True)
    return np.asarray(wiki.draw(jax.random.key(seed), np.uint32(i), size))


def test_wiki_chunks_fill_adjoining_stretches_of_their_own():
    """Chunk i ends where its stretch ends and chunk i+1 starts after
    it: no overlap and no hole, whatever else was drawn."""
    size = 50_000
    span = size * wiki.MEAN_GAP_MS
    a, b = _draw(0, size), _draw(1, size)
    assert int(a[-1]) == int(wiki.T0_MS + span)
    assert int(a[-1]) <= int(b[0]) < int(a[-1]) + 50 * wiki.MEAN_GAP_MS
    assert int(b[-1]) == int(wiki.T0_MS + 2 * span)


def _gap_and_swing(gaps, events_per_gap):
    """Mean gap, and the mean gap at the rate's low over its high: the
    rate 1 + 0.8 sin^2 has period 43,200 events, lowest at phase 0."""
    phase = (np.arange(gaps.size) * events_per_gap) % (wiki.DAY / 2) \
        / (wiki.DAY / 2)
    low = gaps[(phase < 0.1) | (phase > 0.9)].mean()
    high = gaps[(phase > 0.4) & (phase < 0.6)].mean()
    return gaps.mean(), low / high


def test_wiki_mean_gap_and_daily_swing_match_the_original():
    """Against `data/sosd.gen_wiki`, whose keys keep one event in 1.15:
    the same mean gap an event and the same daily rate swing."""
    from repro.data import sosd

    n = 200_000
    raw = _draw(0, int(n * wiki.OVERSAMPLE))
    gap, swing = _gap_and_swing(np.diff(raw.astype(np.float64)), 1.0)
    orig = sosd.gen_wiki(n, seed=5)
    o_gap, o_swing = _gap_and_swing(np.diff(orig.astype(np.float64)),
                                    wiki.OVERSAMPLE)
    assert gap == pytest.approx(o_gap / wiki.OVERSAMPLE, rel=0.03)
    assert swing == pytest.approx(o_swing, rel=0.05)
    assert 1.5 < swing < 1.9                    # rate 1.8 against 1
    keys = wiki.generate(n, 5)
    assert keys[0] == pytest.approx(orig[0], rel=1e-3)


# -- the readers ---------------------------------------------------------
def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, Path(harness.HERE) / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(trace=None, spans=None):
    """A synthetic `harness.Run` of the wiki cell: 8,192 keys answered
    in the window."""
    cfg = json.loads((harness.CHECKOUT / "perfbench" / "configs" /
                      "sosd-wiki-200m-rmi.json").read_text())
    window = SimpleNamespace(in_window=np.ones(8, bool), t_start=0.0,
                             t_end=10.0)
    return harness.Run(cell={"name": WIKI, "chips": 1}, config=cfg,
                       traffic={}, seed=1, seconds=10.0, setup_s=1.0,
                       window=window, keys_per_request=1024, build={},
                       device_kind="TPU v5 lite", spans=spans, trace=trace)


def test_fetch_bytes_are_one_stage2_row():
    cfg = json.loads((harness.CHECKOUT / "perfbench" / "configs" /
                      "sosd-wiki-200m-rmi.json").read_text())
    assert fetch_bytes.fetch_bytes(cfg) == 4 + 4 + 4


def test_rmi_fetch_roofline_reads_as_hand_computed():
    reader = _reader("rmi_fetch_roofline")
    ops = [("rmi_stage2_fetch.3", 0.0, 3000.0), ("bounded_search.1",
                                                  3000.0, 900.0),
           ("fusion.7", 3900.0, 50.0), ("rmi_stage2_fetch.3", 9000.0,
                                        2000.0)]
    trace = {"devices": {"/device:TPU:0": ops}, "t0": 0.0, "t1": 10_000.0}
    # 8,192 keys x 12 bytes at 819 GB/s, over 4,000 ns of fetch kernel
    # (the second operation clipped at the window's end)
    want = 100 * 8192 * 12 / 819e9 * 1e9 / 4000.0
    assert reader.read(_run(trace=trace)) == pytest.approx(want)
    assert 0 < want < 100


def test_rmi_fetch_roofline_sums_over_chips():
    reader = _reader("rmi_fetch_roofline")
    one = [("rmi_stage2_fetch.1", 0.0, 2500.0)]
    trace = {"devices": {"/device:TPU:0": one, "/device:TPU:1": one},
             "t0": 0.0, "t1": 10_000.0}
    want = 100 * 8192 * 12 / 819e9 * 1e9 / 5000.0
    assert reader.read(_run(trace=trace)) == pytest.approx(want)


def test_rmi_fetch_roofline_reads_none_without_the_kernel_or_a_trace():
    reader = _reader("rmi_fetch_roofline")
    assert reader.read(_run()) is None
    trace = {"devices": {"/device:TPU:0": [("bounded_search.1", 0.0, 9.0)]},
             "t0": 0.0, "t1": 10.0}
    assert reader.read(_run(trace=trace)) is None


def test_fused_prepare_s_sums_its_spans_and_reads_none_without():
    from repro.obs.trace import Span

    reader = _reader("fused_prepare_s")
    spans = [Span("index_build", "lifecycle", 0.0, 30.0, 1),
             Span("fused_prepare", "lifecycle", 31.0, 12.5, 1),
             Span("fused_prepare", "lifecycle", 50.0, 0.25, 1),
             Span("launch", "serve", 60.0, 0.002, 2)]
    assert reader.read(_run(spans=spans)) == 12.75
    assert reader.read(_run()) is None
    assert reader.read(_run(spans=spans[:1])) is None


# -- the cells through the harness, on the CPU ----------------------------
def test_wiki_cell_serves_through_the_fused_kernel_end_to_end(tmp_path,
                                                              on_cpu):  # noqa: F811
    bench = _tiny_bench(tmp_path)
    r = harness.run_cell(bench, WIKI, BIG_SEED, 0.6, True, 0.0)
    _assert_line_shape(r, bench, WIKI, "per_layer")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    # spans are read on the CPU; the device metrics need a TPU trace
    assert {"build_s", "fused_prepare_s", "dispatch_ms.probe"} \
        <= set(r["metrics"])
    assert r["metrics"]["fused_prepare_s"]["value"] > 0
    assert "rmi_fetch_roofline" not in r["metrics"]


def test_wiki_cell_index_numbers_show_the_served_window(tmp_path, on_cpu,  # noqa: F811
                                                        capsys):
    bench = _tiny_bench(tmp_path)
    r = harness.run_cell(bench, WIKI, 5, 0.6, False, 0.0)
    assert r["correct"]
    assert set(r["metrics"]) == {"setup_s", "lookups_per_s"}
    line = next(x for x in capsys.readouterr().err.splitlines()
                if " index=" in x)
    index = json.loads(line.split(" index=", 1)[1])
    assert index["kernel_serves"] == 1
    assert 0 < index["served_window"] <= 2048


def test_osm_get_cell_runs_end_to_end(tmp_path, on_cpu):  # noqa: F811
    bench = _tiny_bench(tmp_path)
    r = harness.run_cell(bench, OSM_GET, BIG_SEED, 0.6, False, 0.0)
    _assert_line_shape(r, bench, OSM_GET, "end_to_end")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"setup_s", "p50_ms"}


def test_float32_control_fails_the_wiki_cell_where_the_program_passes(
        tmp_path, on_cpu):  # noqa: F811
    bench = _tiny_bench(tmp_path)
    (tmp_path / "keysets").mkdir()
    (tmp_path / "keysets" / "dense.py").write_text(DENSE)
    path = tmp_path / bench.spec["configs"][2]["file"]
    cfg = json.loads(path.read_text())
    assert cfg["name"] == "sosd-wiki-200m-rmi"
    cfg["keys"]["recipe"] = "dense"
    path.write_text(json.dumps(cfg))
    r = harness.run_cell(bench, WIKI, 31, 0.6, False, 0.0,
                         control=control.float32_lower_bound)
    assert r["correct"]
    assert all(c["value"] == 0 for c in r["checks"].values())
    assert r["control"]["wrong_answers"] > 0


def test_wiki_recipe_tops_up_from_its_own_stream():
    """A top-up chunk continues the timeline: every key past the first
    chunk's stretch lies in the stretches after it."""
    keys = keygen.unique_sorted(wiki.draw, N, 3, oversample=1.0,
                                chunk=4096)
    assert keys.shape == (N,) and np.all(keys[1:] > keys[:-1])
    assert int(keys[-1]) <= wiki.T0_MS + \
        (N // 4096 + 2) * 4096 * wiki.MEAN_GAP_MS


def test_rmi_served_through_lookup_service_matches_the_reference():
    """The configuration's service (async, health on, Pallas in
    interpret mode) over 2^18 wiki keys at 2^10 leaves: present keys,
    absent keys between them, and keys below the least and above the
    greatest get the reference's lower-bound ranks."""
    from repro.core.spec import IndexSpec
    from repro.serve.lookup import LookupService, LookupServiceConfig

    cfg = json.loads((harness.CHECKOUT / "perfbench" / "configs" /
                      "sosd-wiki-200m-rmi.json").read_text())
    reference = harness.Bench().module("references", cfg["reference"])
    keys = wiki.generate(1 << 18, BIG_SEED)
    s = cfg["service"]
    svc = LookupService(keys, LookupServiceConfig(
        spec=IndexSpec("rmi", dict(cfg["index"]["hyper"], branching=1 << 10),
                       backend=s["backend"]),
        executor=s["executor"], max_batch=s["max_batch"],
        deadline_ms=s["deadline_ms"], health=s["health"], slots=s["slots"],
        warm_buckets=(1024, 2048)))
    rng = np.random.default_rng(3)
    present = keys[rng.integers(0, keys.size, 3000)]
    absent = np.setdiff1d(present + np.uint64(1), keys)
    edges = np.array([0, 1, int(keys[0]) - 1, int(keys[0]),
                      int(keys[-1]), int(keys[-1]) + 1, 2**63, 2**64 - 1],
                     np.uint64)
    q = np.concatenate([present, absent, edges])
    with svc:
        assert svc.generation.build.meta["kernel_serves"] == 1
        got = np.concatenate([svc.submit(q[i:i + 1024]).result(120)
                              for i in range(0, q.size, 1024)])
    assert absent.size > 1000
    np.testing.assert_array_equal(got, reference.answers(keys, q))
