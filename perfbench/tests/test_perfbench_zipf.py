"""YCSB's scrambled zipfian sampler (`perfbench/zipf.py`)."""
import numpy as np

from perfbench import zipf


def _fnv_reference(v: int) -> int:
    """YCSB's Utils.fnvhash64 in plain Python integers."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= v & 0xFF
        v >>= 8
        h = (h * 1099511628211) & (2**64 - 1)
    if h >= 2**63:
        h -= 2**64
    return abs(h)


def test_fnvhash64_matches_ycsb():
    vals = [0, 1, 2, 255, 256, 10**9, 9_999_999_999]
    got = zipf.fnvhash64(np.asarray(vals, np.int64)).tolist()
    assert got == [_fnv_reference(v) for v in vals]


def test_top_ranked_share_matches_theta():
    """Rank 0 of Gray et al.'s generator takes 1 / zeta(10^10, 0.99) of
    the draws (3.78%); the scrambled key it hashes to takes that plus the
    ~1/n of the tail that hashes onto it."""
    n_items, size = 1_000_000, 400_000
    ranks = zipf.scrambled_zipfian(np.random.default_rng(3), size, n_items)
    assert ranks.min() >= 0 and ranks.max() < n_items
    top = np.bincount(ranks, minlength=n_items).max() / size
    p = 1.0 / zipf.ZETAN
    sigma = np.sqrt(p * (1 - p) / size)
    assert abs(top - p) < 5 * sigma + 2.0 / n_items


def test_unscrambled_ranks_fall_like_a_power_of_theta():
    """P(rank 1) / P(rank 0) = 2^-theta under the generator's two exact
    first steps."""
    r = zipf.zipfian(np.random.default_rng(5), 2_000_000)
    c0, c1 = np.count_nonzero(r == 0), np.count_nonzero(r == 1)
    assert abs(c1 / c0 - 0.5**zipf.THETA) < 0.02


def test_no_per_item_table():
    """Ranks over 200M items come without materialising 200M weights."""
    ranks = zipf.scrambled_zipfian(np.random.default_rng(1), 1000,
                                   200_000_000)
    assert ranks.shape == (1000,) and ranks.max() < 200_000_000
