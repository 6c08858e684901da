"""Exactly ``n`` sorted unique uint64 keys from a seeded raw-value stream.

A key-set recipe (`keysets/<name>.py`) supplies ``draw(root, i, size)``:
a jittable function that returns chunk ``i`` of the raw uint64 values of
the key set whose `jax.random` key is ``root`` (what the whole set
shares, such as cluster centres, comes from ``root``; what one chunk
draws, from ``fold_in(root, i)``).  Raw values are drawn on the device in fixed-size
chunks, each chunk sorted there, and the sorted chunks merged on the
host (a stable sort of a few sorted runs is a merge).  Duplicates are
dropped; when fewer than ``n`` distinct values remain, further chunks
of the same stream are drawn, so a top-up always comes from the
recipe's own distribution.  Of the ``u >= n`` distinct values, the ones
at evenly spaced ranks ``floor(i * u / n)`` are kept, which keeps the
shape of the CDF.

Chunks bound the device memory the generation takes to a few times one
chunk, well under what the served key set holds afterwards.
"""
from __future__ import annotations

import functools

import numpy as np

#: raw values drawn per device call
CHUNK = 1 << 25


@functools.lru_cache(maxsize=None)
def _sorted_chunk(draw, size: int):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda root, i: jnp.sort(draw(root, i, size)))


def unique_sorted(draw, n: int, seed: int, oversample: float,
                  chunk: int = CHUNK) -> np.ndarray:
    """``n`` sorted unique uint64 keys drawn by ``draw`` from ``seed``."""
    import jax

    jax.config.update("jax_enable_x64", True)     # uint64 keys, f64 draws
    if n < 1:
        raise ValueError("n must be >= 1")
    chunk = int(min(chunk, max(1024, int(n * oversample))))
    root = jax.random.key(int(seed))
    fn = _sorted_chunk(draw, chunk)
    runs = []
    want = int(np.ceil(n * oversample / chunk))
    while True:
        while len(runs) < want:
            runs.append(np.asarray(fn(root, np.uint32(len(runs)))))
        raw = np.concatenate(runs)
        raw.sort(kind="stable")          # a merge of sorted runs
        keep = np.empty(raw.size, bool)
        keep[0] = True
        np.not_equal(raw[1:], raw[:-1], out=keep[1:])
        u = int(np.count_nonzero(keep))
        if u >= n:
            break
        # about u / len(runs) new distinct values per further chunk
        want = len(runs) + int(np.ceil(1.1 * (n - u) * len(runs) / u))
    uniq = raw[keep]
    del raw, keep
    pos = (np.arange(n, dtype=np.int64) * u) // n
    return uniq[pos]
