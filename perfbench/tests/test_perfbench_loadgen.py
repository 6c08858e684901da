"""The traffic generator: requests drawn from the seed, arrivals that
give every seed the same work."""
import numpy as np

from perfbench import loadgen

KEYS = np.arange(10, 10 + 5000 * 7, 7, dtype=np.uint64)
OPEN = {"loop": "open", "rate_per_s": 2000, "keys_per_request": 1,
        "present_frac": 1.0, "present_ranks": "ycsb_scrambled_zipfian"}
CLOSED = {"loop": "closed", "callers": 4, "keys_per_request": 64,
          "present_frac": 0.8, "present_ranks": "uniform",
          "absent_margin": 1000, "pool_requests": 16}


def test_same_seed_same_requests():
    for params in (OPEN, CLOSED):
        a = loadgen.make(params, KEYS, 3, 2.0)
        b = loadgen.make(params, KEYS, 3, 2.0)
        assert np.array_equal(a.queries, b.queries)
        c = loadgen.make(params, KEYS, 4, 2.0)
        assert not np.array_equal(a.queries, c.queries)


def test_open_loop_offers_every_seed_the_same_gaps_in_another_order():
    a = loadgen.make(OPEN, KEYS, 1, 2.0).offsets
    b = loadgen.make(OPEN, KEYS, 2, 2.0).offsets
    assert a.size == b.size == 4000
    ga, gb = np.diff(a, prepend=0.0), np.diff(b, prepend=0.0)
    assert np.allclose(np.sort(ga), np.sort(gb), rtol=1e-9, atol=0)
    assert not np.allclose(ga, gb)
    assert 0 < a[0] and a[-1] < 2.0
    assert abs(np.mean(ga) - 1 / 2000) < 1e-5


def test_closed_loop_pool_mixes_present_and_absent_keys():
    t = loadgen.make(CLOSED, KEYS, 5, 1.0)
    assert t.queries.shape == (16, 64)
    present = np.isin(t.queries, KEYS).mean()
    assert 0.75 <= present <= 0.85                 # absent keys rarely hit
    assert int(t.queries.min()) >= int(KEYS[0]) - 1000
    assert int(t.queries.max()) <= int(KEYS[-1]) + 1000


class _Echo:
    """A service that answers each request at once with its keys."""

    class _Done:
        def __init__(self, v):
            self.v = v

        def result(self, timeout=None):
            return self.v

    def submit(self, keys):
        return self._Done(np.asarray(keys).astype(np.int64))


def test_windows_record_every_request():
    for params in (OPEN, CLOSED):
        t = loadgen.make(params, KEYS, 6, 0.3)
        w = loadgen.run(_Echo(), t, 0.3)
        assert w.errors == 0 and all(a is not None for a in w.answers)
        assert np.all(w.t_done >= w.t_due)
        for r, a in zip(w.request, w.answers):
            assert np.array_equal(a, t.queries[r].astype(np.int64))
