"""The trip-count-aware HLO analyzer must match ground truth exactly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks import hlo_cost


def _body(x, w):
    return jnp.tanh(x @ w), None


def test_scan_trip_counts_recovered():
    def scanned(x, ws):
        x, _ = jax.lax.scan(_body, x, ws)
        return x

    x = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((6, 256, 256), jnp.float32)
    c = jax.jit(scanned).lower(x, ws).compile()
    r = hlo_cost.analyze(c.as_text())
    expected = 6 * 2 * 64 * 256 * 256
    assert r["flops"] == pytest.approx(expected, rel=1e-6)
    # and the naive xla counter under-reports by exactly the trip count
    assert c.cost_analysis()["flops"] == pytest.approx(expected / 6, rel=1e-6)


def test_unrolled_matches_xla():
    def unrolled(x, ws):
        for i in range(4):
            x, _ = _body(x, ws[i])
        return x

    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((4, 128, 128), jnp.float32)
    c = jax.jit(unrolled).lower(x, ws).compile()
    r = hlo_cost.analyze(c.as_text())
    assert r["flops"] == pytest.approx(c.cost_analysis()["flops"], rel=1e-6)


def test_nested_scan_multiplies():
    def inner(x, w):
        x, _ = jax.lax.scan(_body, x, w)
        return x, None

    def outer(x, ws):
        x, _ = jax.lax.scan(inner, x, ws)
        return x

    x = jax.ShapeDtypeStruct((16, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 5, 64, 64), jnp.float32)
    c = jax.jit(outer).lower(x, ws).compile()
    r = hlo_cost.analyze(c.as_text())
    assert r["flops"] == pytest.approx(15 * 2 * 16 * 64 * 64, rel=1e-6)


def test_shape_bytes_parser():
    assert hlo_cost._shape_bytes("f32[8,16]{1,0}") == 8 * 16 * 4
    assert hlo_cost._shape_bytes("(bf16[4,4], s32[2])") == 32 + 8
    assert hlo_cost._shape_bytes("pred[100]") == 100
