"""Pallas kernels vs pure-jnp oracles, interpret mode, shape/dtype sweeps."""
import jax

jax.config.update("jax_enable_x64", True)  # uint64 key planes

import numpy as np
import jax.numpy as jnp
import pytest

from repro.data import sosd
from repro.kernels.common import split_u64, merge_u64, pad_pow2
from repro.kernels.bounded_search.ops import lower_bound_windows
from repro.kernels.bounded_search.ref import lower_bound_windows_ref
from repro.kernels.rmi_lookup import ops as rops
from repro.kernels.rmi_lookup import ref as rref


def test_split_merge_roundtrip():
    rng = np.random.default_rng(0)
    a = rng.integers(0, 2**63, 1000, dtype=np.uint64)
    hi, lo = split_u64(a)
    assert (merge_u64(hi, lo) == a).all()
    b = rng.integers(0, 2**31, 1000).astype(np.int32)
    hi32, lo32 = split_u64(b)
    assert (hi32 == 0).all() and (lo32 == b.astype(np.uint32)).all()


@pytest.mark.parametrize("n,m,width", [
    (1_000, 257, 64), (10_000, 2_048, 160), (50_000, 4_001, 512),
])
@pytest.mark.parametrize("dtype", [np.uint64, np.uint32])
def test_bounded_search_shapes_dtypes(n, m, width, dtype):
    rng = np.random.default_rng(n + m)
    if dtype == np.uint64:
        keys = np.unique(rng.integers(0, 2**62, int(n * 1.2), dtype=np.uint64))[:n]
    else:
        keys = np.unique(rng.integers(0, 2**31, int(n * 1.3)).astype(np.uint32))[:n]
    q = keys[rng.integers(0, len(keys), m)]
    lb = np.searchsorted(keys, q).astype(np.int64)
    lo = np.maximum(lb - rng.integers(0, width - 1, m), 0)
    got = lower_bound_windows(jnp.asarray(keys), jnp.asarray(q),
                              jnp.asarray(lo, jnp.int32), max_width=width,
                              interpret=True)
    ref = lower_bound_windows_ref(jnp.asarray(keys), jnp.asarray(q), lo, width)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_bounded_search_overflow_fallback():
    """Every query in ONE tile (the old per-tile capacity overflow case):
    the per-query grid re-uses the resident tile and stays exact."""
    keys = np.arange(10_000, dtype=np.uint64) * 3 + 5
    q = keys[np.random.default_rng(0).integers(0, 100, 5_000)]  # tile 0 only
    lb = np.searchsorted(keys, q).astype(np.int64)
    lo = np.maximum(lb - 10, 0)
    got = lower_bound_windows(jnp.asarray(keys), jnp.asarray(q),
                              jnp.asarray(lo, jnp.int32), max_width=64,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(got), lb)


def test_bounded_search_wide_window_fallback():
    """max_width > DATA_TILE falls back to the exact jnp path."""
    keys = np.unique(np.random.default_rng(1).integers(
        0, 2**40, 8_000, dtype=np.uint64))
    q = keys[::3]
    lb = np.searchsorted(keys, q).astype(np.int64)
    lo = np.zeros(len(q), np.int64)
    got = lower_bound_windows(jnp.asarray(keys), jnp.asarray(q),
                              jnp.asarray(lo, jnp.int32),
                              max_width=len(keys) + 1, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), lb)


@pytest.mark.parametrize("ds", ["wiki", "face", "osm"])
@pytest.mark.parametrize("branching", [512, 4096])
def test_rmi_kernel_end_to_end(ds, branching):
    keys = sosd.generate(ds, 40_000, seed=3)
    q = sosd.make_queries(keys, 4_096, seed=5, present_frac=0.5)
    lb = np.searchsorted(keys, q)
    st = rops.prepare_f32_state(keys, branching=branching)
    blo, bhi = rops.rmi_bounds(st, jnp.asarray(q), interpret=True)
    blo, bhi = np.asarray(blo), np.asarray(bhi)
    assert ((blo <= lb) & (lb <= bhi)).all(), "f32 bounds must stay valid"
    pos = rops.rmi_lookup(st, jnp.asarray(keys), jnp.asarray(q), interpret=True)
    np.testing.assert_array_equal(np.asarray(pos), lb)


def test_rmi_kernel_vs_ref_inference():
    keys = sosd.generate("amzn", 30_000, seed=9)
    q = sosd.make_queries(keys, 2_000, seed=10)
    st = rops.prepare_f32_state(keys, branching=1024)
    lo_k, hi_k = rops.rmi_bounds(st, jnp.asarray(q), interpret=True)
    lo_r, hi_r = rref.rmi_bounds_ref(st, jnp.asarray(q), st.n)
    np.testing.assert_array_equal(np.asarray(lo_k), np.asarray(lo_r))
    np.testing.assert_array_equal(np.asarray(hi_k), np.asarray(hi_r))


def test_pad_pow2():
    assert pad_pow2(1) == 128
    assert pad_pow2(129) == 256
    assert pad_pow2(4096) == 4096
