"""SOSD "books" surrogate: book-popularity keys (the amzn recipe).

The recipe of the repository's `data/sosd.gen_amzn`, copied so that the
yardstick does not move with the program: a lognormal body
(``exp(10 + 2.2 z)``) and a Pareto tail (shape 1.1, scale ``e^14``) in
the ratio 20:1, scaled up and floored to uint64.  A smooth heavy-tailed
CDF that is locally near-linear.

One departure, for scale.  The original divides by the sample maximum,
which at 200M keys comes from the Pareto tail and swings by several
times from seed to seed; it then packs the body so tightly that about
fifty draws land on each integer near the median, and most of the key
set has to come from a top-up.  Here the scale is fixed: the body's
4.5-sigma point maps to 2^47, as the recipe's "scaled to ~2^47" intends.
The densest integer then draws under a tenth of a key, every seed has
the same scale, and tail values past 2^63 are clipped there.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

MU, SIGMA = 10.0, 2.2            # lognormal body
TAIL_SHAPE, TAIL_SCALE = 1.1, math.exp(14.0)
TAIL_SHARE = 1 / 21              # body : tail = 20 : 1 (m and m // 20)
SCALE = 2.0**47 / math.exp(MU + 4.5 * SIGMA)
OVERSAMPLE = 1.3125              # m = 1.25 n body draws plus m // 20 tail


def draw(root, i, size: int):
    kb, kt = jax.random.split(jax.random.fold_in(root, i))
    n_tail = int(size * TAIL_SHARE)
    body = jnp.exp(MU + SIGMA * jax.random.normal(kb, (size - n_tail,),
                                                  jnp.float64))
    u = jax.random.uniform(kt, (n_tail,), jnp.float64,
                           minval=jnp.finfo(jnp.float64).tiny, maxval=1.0)
    tail = u ** (-1.0 / TAIL_SHAPE) * TAIL_SCALE
    raw = jnp.concatenate([body, tail]) * SCALE
    raw = jnp.clip(raw, 1.0, 2.0**63)
    return raw.astype(jnp.uint64)


def generate(n: int, seed: int):
    from perfbench.keygen import unique_sorted

    return unique_sorted(draw, n, seed, OVERSAMPLE)
