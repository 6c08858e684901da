"""The control of `correct`, at a size a test run holds: the reference one
precision down (float32 keys) fails the check that every sound run
passes."""
import numpy as np
import pytest

from perfbench import control
from perfbench.tests.test_perfbench_harness import ROUTED, _tiny_bench
from perfbench.tests.test_perfbench_harness import on_cpu  # noqa: F401


#: At 200M keys the surrogates' neighbours lie closer than float32 can
#: tell apart; a test-size key set is made as dense by a recipe of its
#: own, found by name like any other.
DENSE = """
import jax


def generate(n, seed):
    from perfbench.keygen import unique_sorted

    def draw(root, i, size):
        v = jax.random.randint(jax.random.fold_in(root, i), (size,), 0,
                               2 * n)
        return v.astype("uint64") * 3 + (1 << 40)

    return unique_sorted(draw, n, seed, 4.0)
"""


@pytest.mark.parametrize("cell", ["books-pgm.probe1024", "osm-rs.probe1024",
                                  "books-pgm.get-zipf", ROUTED])
def test_float32_control_fails_where_the_program_passes(tmp_path, on_cpu,  # noqa: F811
                                                        cell):
    import json

    from perfbench import harness

    bench = _tiny_bench(tmp_path)
    (tmp_path / "keysets").mkdir()
    (tmp_path / "keysets" / "dense.py").write_text(DENSE)
    for c in bench.spec["configs"]:
        path = tmp_path / c["file"]
        cfg = json.loads(path.read_text())
        cfg["keys"]["recipe"] = "dense"
        path.write_text(json.dumps(cfg))
    r = harness.run_cell(bench, cell, 31, 0.6, False, 0.0,
                         control=control.float32_lower_bound)
    assert r["correct"]
    assert all(c["value"] == 0 for c in r["checks"].values())
    assert r["control"]["wrong_answers"] > 0


def test_float32_lower_bound_rounds_keys():
    keys = np.array([2**40, 2**40 + 1, 2**40 + 2], np.uint64)
    got = control.float32_lower_bound(keys, keys[None, :])
    assert got.tolist() == [[0, 0, 0]]             # three keys, one float
