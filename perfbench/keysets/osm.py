"""SOSD "osm_cellids" surrogate: Hilbert cell ids of clustered points.

The recipe of the repository's `data/sosd.gen_osm`, copied so that the
yardstick does not move with the program: 256 cluster centres uniform
on a 2^24 x 2^24 grid with Pareto(1) + 0.05 weights ("cities"), each
point Gaussian around its centre (sigma = side / 400), 8% of the points
uniform over the grid ("background"), and each point's key its
order-24 Hilbert distance.  Globally smooth, locally erratic: the data
set learned models find hardest in the paper.

Coordinates are drawn in float32 and floored to uint32 (the grid is
2^24 wide, so float32 holds every cell), and the centres and weights
come from the seed's stream apart from the points'.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ORDER = 24
SIDE = 1 << ORDER
CLUSTERS = 256
SPREAD = SIDE / 400.0
BACKGROUND = 0.08
OVERSAMPLE = 1.3                 # m = 1.3 n points
#: ``fold_in`` tag of the centres' stream; chunk streams use 0, 1, 2, ...
_CENTRES = 0xFFFFFFFF


def hilbert_d(x, y, order: int = ORDER):
    """Hilbert distance of uint32 grid cells (the classic xy2d), uint64."""
    side = jnp.uint32((1 << order) - 1)
    d = jnp.zeros(x.shape, jnp.uint64)
    for bit in range(order - 1, -1, -1):
        s = jnp.uint32(1 << bit)
        rx = ((x & s) > 0).astype(jnp.uint32)
        ry = ((y & s) > 0).astype(jnp.uint32)
        d = d + jnp.uint64((1 << bit) ** 2) * ((jnp.uint32(3) * rx) ^ ry
                                                ).astype(jnp.uint64)
        swap = ry == 0
        flip = swap & (rx == 1)
        xf = jnp.where(flip, side - x, x)
        yf = jnp.where(flip, side - y, y)
        x, y = jnp.where(swap, yf, xf), jnp.where(swap, xf, yf)
    return d


def draw(root, i, size: int):
    kc, kw = jax.random.split(jax.random.fold_in(root, _CENTRES))
    cx, cy = jax.random.uniform(kc, (2, CLUSTERS), jnp.float32, 0.0, SIDE)
    u = jax.random.uniform(kw, (CLUSTERS,), jnp.float32,
                           minval=jnp.finfo(jnp.float32).tiny, maxval=1.0)
    weights = 1.0 / u - 1.0 + 0.05           # Pareto(1) (numpy's form) + 0.05
    cdf = jnp.cumsum(weights) / jnp.sum(weights)

    ka, kx, kb, kg = jax.random.split(jax.random.fold_in(root, i), 4)
    # searchsorted(cdf, v) and cx[which] without gathers, which take
    # seconds per chunk on a TPU: the count of cdf values below v, and a
    # one-hot select (exact: every term of the sum but one is zero).
    v = jax.random.uniform(ka, (size,))
    which = jnp.minimum(jnp.sum(cdf < v[:, None], axis=1, dtype=jnp.int32),
                        CLUSTERS - 1)
    onehot = which[:, None] == jnp.arange(CLUSTERS, dtype=jnp.int32)
    noise = jax.random.normal(kx, (2, size), jnp.float32) * SPREAD
    x = jnp.sum(jnp.where(onehot, cx, 0.0), axis=1) + noise[0]
    y = jnp.sum(jnp.where(onehot, cy, 0.0), axis=1) + noise[1]
    x = jnp.clip(x, 0, SIDE - 1).astype(jnp.uint32)
    y = jnp.clip(y, 0, SIDE - 1).astype(jnp.uint32)
    bg = jax.random.uniform(kb, (size,)) < BACKGROUND
    gx, gy = jax.random.randint(kg, (2, size), 0, SIDE, jnp.uint32)
    return hilbert_d(jnp.where(bg, gx, x), jnp.where(bg, gy, y))


def generate(n: int, seed: int):
    from perfbench.keygen import unique_sorted

    return unique_sorted(draw, n, seed, OVERSAMPLE)
