"""The benchmark's own key-set generators, at small n on the CPU."""
import numpy as np
import pytest

from perfbench import keygen
from perfbench.keysets import amzn, osm

N = 20_000
BIG_SEED = 2**31 + 12345           # seeds run past 32 signed bits


@pytest.mark.parametrize("recipe", [amzn, osm], ids=["amzn", "osm"])
def test_exactly_n_sorted_unique_keys(recipe):
    keys = recipe.generate(N, BIG_SEED)
    assert keys.dtype == np.uint64 and keys.shape == (N,)
    assert np.all(keys[1:] > keys[:-1])


@pytest.mark.parametrize("recipe", [amzn, osm], ids=["amzn", "osm"])
def test_same_seed_same_keys_other_seed_other_keys(recipe):
    a = recipe.generate(N, 7)
    assert np.array_equal(a, recipe.generate(N, 7))
    assert not np.array_equal(a, recipe.generate(N, 8))


def test_amzn_scale_is_fixed_not_the_sample_maximum():
    """The body sits at the same place for every seed: the median key
    moves by sampling noise only."""
    med = [float(np.median(amzn.generate(N, s))) for s in (1, 2, 3)]
    assert max(med) / min(med) < 1.2


def test_osm_keys_are_hilbert_cells_of_the_grid():
    keys = osm.generate(N, 3)
    assert int(keys[-1]) < 1 << (2 * osm.ORDER)


def test_hilbert_matches_the_curve_of_order_one():
    import jax.numpy as jnp

    x = jnp.asarray([0, 0, 1, 1], jnp.uint32)
    y = jnp.asarray([0, 1, 1, 0], jnp.uint32)
    assert np.asarray(osm.hilbert_d(x, y, order=1)).tolist() == [0, 1, 2, 3]


def test_osm_draw_picks_clusters_as_searchsorted_and_indexing_do():
    """The draw's gather-free cluster pick gives the very points that
    ``searchsorted`` and indexing give."""
    import jax
    import jax.numpy as jnp

    root, size = jax.random.key(BIG_SEED), 50_000
    kc, kw = jax.random.split(jax.random.fold_in(root, osm._CENTRES))
    cx, cy = jax.random.uniform(kc, (2, osm.CLUSTERS), jnp.float32, 0.0,
                                osm.SIDE)
    u = jax.random.uniform(kw, (osm.CLUSTERS,), jnp.float32,
                           minval=jnp.finfo(jnp.float32).tiny, maxval=1.0)
    weights = 1.0 / u - 1.0 + 0.05
    cdf = jnp.cumsum(weights) / jnp.sum(weights)
    ka, kx, kb, kg = jax.random.split(jax.random.fold_in(root, 3), 4)
    which = jnp.minimum(jnp.searchsorted(cdf, jax.random.uniform(ka, (size,))),
                        osm.CLUSTERS - 1)
    noise = jax.random.normal(kx, (2, size), jnp.float32) * osm.SPREAD
    x = jnp.clip(cx[which] + noise[0], 0, osm.SIDE - 1).astype(jnp.uint32)
    y = jnp.clip(cy[which] + noise[1], 0, osm.SIDE - 1).astype(jnp.uint32)
    bg = jax.random.uniform(kb, (size,)) < osm.BACKGROUND
    gx, gy = jax.random.randint(kg, (2, size), 0, osm.SIDE, jnp.uint32)
    want = osm.hilbert_d(jnp.where(bg, gx, x), jnp.where(bg, gy, y))
    got = osm.draw(root, np.uint32(3), size)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def _crowded(root, i, size):
    """Raw values from [0, 1.5 * N): far more collisions than distinct."""
    import jax

    return jax.random.randint(jax.random.fold_in(root, i), (size,), 0,
                              int(1.5 * N)).astype("uint64")


def test_top_up_draws_from_the_same_distribution():
    keys = keygen.unique_sorted(_crowded, N, 5, oversample=1.0, chunk=4096)
    assert keys.shape == (N,) and np.all(keys[1:] > keys[:-1])
    assert int(keys[-1]) < int(1.5 * N)        # never outside the stream
