"""SOSD "wiki_ts" surrogate: Wikipedia edit timestamps (the wiki recipe).

The recipe of the repository's `data/sosd.gen_wiki`, copied so that the
yardstick does not move with the program.  Event ``t`` follows the one
before it by an exponential gap of mean 1000 ms over a rate
``1 + 0.8 sin^2(2 pi t / 86400)``, a daily swing in the event index as
the original has it; on one event in 200 a burst adds an exponential
term of mean 50 to the rate, one short gap.  Keys are the timestamps in
milliseconds from 1e9, floored to uint64: a smooth, near-linear CDF
with daily rate swings and short bursts, about 2^37 wide at 200M keys.

Departures, so that any chunk can be drawn on the device alone.  The
original sums every gap before an event, so chunk ``i`` would need the
sums of all earlier chunks.  Here chunk ``i`` of ``size`` events fills a
fixed stretch of time of its own, ``size * MEAN_GAP_MS`` long, starting
``i`` stretches after 1e9: its cumulative gaps are scaled to end exactly
at the stretch's end.  The scale is 1 within sampling noise (a chunk of
2^25 gaps sums to its mean within about 0.02%), and chunks neither
overlap nor leave holes.  A burst falls on each event with probability
1/200, where the original picks exactly one event in 200 without
replacement.  Collisions are topped up from the recipe's own stream
(`keygen`), not from uniform values in [1, 2^62).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

T0_MS = 1.0e9                    # the first stretch starts here
GAP_MS = 1000.0                  # mean gap at rate 1
DAY = 86400                      # events a rate period spans
SWING = 0.8                      # rate 1 + SWING sin^2(...)
BURST_SHARE = 1 / 200
BURST_RATE = 50.0                # mean of the burst's added rate
#: expected gap, ms: GAP_MS * E[1 / rate] over the daily phase and the
#: bursts; (1 - 1/200) / sqrt(1.8) + (1/200) * 0.0626521 (the burst
#: term's mean inverse rate, by quadrature)
MEAN_GAP_MS = GAP_MS * ((1 - BURST_SHARE) / math.sqrt(1 + SWING)
                        + BURST_SHARE * 0.0626521)
OVERSAMPLE = 1.15                # m = 1.15 n events, as the original


def draw(root, i, size: int):
    ke, kb, kx = jax.random.split(jax.random.fold_in(root, i), 3)
    t = (jnp.asarray(i, jnp.int64) * size
         + jnp.arange(size, dtype=jnp.int64)) % DAY
    rate = 1.0 + SWING * jnp.sin(2 * jnp.pi * t.astype(jnp.float64)
                                 / DAY) ** 2
    burst = jax.random.uniform(kb, (size,), jnp.float64) < BURST_SHARE
    rate = rate + jnp.where(
        burst, BURST_RATE * jax.random.exponential(kx, (size,), jnp.float64),
        0.0)
    gaps = jax.random.exponential(ke, (size,), jnp.float64) / rate
    csum = jnp.cumsum(gaps)
    span = size * MEAN_GAP_MS
    start = T0_MS + jnp.asarray(i, jnp.float64) * span
    return jnp.floor(start + span * (csum / csum[-1])).astype(jnp.uint64)


def generate(n: int, seed: int):
    from perfbench.keygen import unique_sorted

    return unique_sorted(draw, n, seed, OVERSAMPLE)
