"""YCSB's scrambled zipfian rank sampler, vectorised.

YCSB's `ScrambledZipfianGenerator` (Cooper et al., SoCC 2010) draws from
a `ZipfianGenerator` (Gray et al., "Quickly generating billion-record
synthetic databases", SIGMOD 1994) over a fixed universe of 10^10 items
with a precomputed zeta, hashes the drawn item with 64-bit FNV, and
takes it modulo the item count.  The hot items are then spread over the
key space, and no table of per-item weights is ever built, whatever the
item count.  Constants are YCSB's own.
"""
from __future__ import annotations

import numpy as np

THETA = 0.99                      # YCSB's ZIPFIAN_CONSTANT
UNIVERSE = 10_000_000_000         # ScrambledZipfianGenerator.ITEM_COUNT
ZETAN = 26.46902820178302         # zeta(ITEM_COUNT, 0.99), as YCSB has it
_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)


def zipfian(rng: np.random.Generator, size: int, items: int = UNIVERSE,
            zetan: float = ZETAN, theta: float = THETA) -> np.ndarray:
    """Gray et al.'s generator as YCSB's `ZipfianGenerator.nextLong`:
    int64 ranks in ``[0, items)``, rank 0 the most popular."""
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5**theta
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(size)
    uz = u * zetan
    ranks = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ranks = np.where(uz < zeta2, 1, ranks)
    return np.where(uz < 1.0, 0, ranks)


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB's `Utils.fnvhash64`: FNV-1a over the 8 little-endian bytes,
    then the absolute value of the signed result."""
    v = np.asarray(v).astype(np.uint64)
    h = np.full(v.shape, _FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for i in range(8):
            h = (h ^ ((v >> np.uint64(8 * i)) & np.uint64(0xFF))) * _FNV_PRIME
    return np.abs(h.view(np.int64))


def scrambled_zipfian(rng: np.random.Generator, size: int,
                      n_items: int) -> np.ndarray:
    """int64 ranks in ``[0, n_items)``, YCSB's scrambled zipfian."""
    return fnvhash64(zipfian(rng, size)) % np.int64(n_items)
