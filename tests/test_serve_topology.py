"""Range-routed shard mesh tests (DESIGN.md §16): topology routing
algebra, routed-vs-broadcast bit-parity across the index x executor
matrix, split-point/absent-key edge cases, single-shard degeneration,
boundary-crossing scans, replica rebalance, per-shard observability,
and the pinned host staging contract."""
import numpy as np
import pytest

from repro.core import base
from repro.core.spec import IndexSpec, Tuner
from repro.serve.lookup import (LookupService, LookupServiceConfig,
                                MutableLookupService,
                                MutableLookupServiceConfig, ShardTopology)


def _oracle(keys, q):
    return base.lower_bound_oracle(keys, q)


# ---------------------------------------------------------------------------
# topology value object: routing algebra (no service, no jit)
# ---------------------------------------------------------------------------
def test_route_split_points_side_left():
    # split_points[s] IS shard s's last key: a query equal to it must
    # route to shard s (side='left'), the next key up to shard s+1
    keys = np.arange(0, 1000, 2, dtype=np.uint64)  # evens, gaps of 1
    topo = ShardTopology.from_keys(keys, 4)
    for s, split in enumerate(topo.split_points):
        assert topo.route(np.array([split], dtype=np.uint64))[0] == s
        assert topo.route(np.array([split + 1], dtype=np.uint64))[0] == s + 1
        # the split key itself lives at the end of shard s's slice
        lo, hi = topo.offsets[s], topo.offsets[s + 1]
        assert keys[hi - 1] == split


def test_route_extremes():
    keys = (np.arange(100, dtype=np.uint64) + 50) * 10
    topo = ShardTopology.from_keys(keys, 5)
    q = np.array([0, keys[0] - 1, keys[-1] + 1, 2**64 - 1], dtype=np.uint64)
    sid = topo.route(q)
    assert sid[0] == 0 and sid[1] == 0            # below global min
    assert sid[2] == topo.n_shards - 1            # above global max
    assert sid[3] == topo.n_shards - 1


def test_duplicates_never_straddle_a_split():
    # 50 distinct values x 40 duplicates each: every boundary must sit
    # at the FIRST occurrence of its key, so no duplicate run straddles
    rng = np.random.default_rng(3)
    vals = np.sort(rng.choice(10_000, size=50, replace=False))
    keys = np.sort(np.repeat(vals, 40).astype(np.uint64))
    topo = ShardTopology.from_keys(keys, 8)
    for s in range(1, topo.n_shards):
        o = topo.offsets[s]
        assert keys[o - 1] != keys[o]
    # and routed ranks stay globally exact on the duplicated values
    q = keys[rng.integers(0, keys.size, 500)]
    sid = topo.route(q)
    pos = np.empty(q.size, dtype=np.int64)
    for s in range(topo.n_shards):
        m = sid == s
        lo, hi = topo.offsets[s], topo.offsets[s + 1]
        pos[m] = lo + np.searchsorted(keys[lo:hi], q[m], side="left")
    assert np.array_equal(pos, _oracle(keys, q))


def test_route_device_matches_host_on_boundaries():
    keys = np.sort(np.random.default_rng(5).choice(
        2**40, size=4096, replace=False).astype(np.uint64))
    topo = ShardTopology.from_keys(keys, 6)
    q = np.concatenate([topo.split_points,
                        topo.split_points - 1,
                        topo.split_points + 1,
                        np.array([0, 2**63], dtype=np.uint64)])
    import jax.numpy as jnp

    dev = np.asarray(topo.route_device(jnp.asarray(q)), dtype=np.int64)
    assert np.array_equal(dev, topo.route(q))


def test_single_topology_routes_everything_to_shard_zero():
    topo = ShardTopology.single(1000)
    assert topo.n_shards == 1
    q = np.array([0, 7, 2**63], dtype=np.uint64)
    assert np.array_equal(topo.route(q), np.zeros(3, dtype=np.int64))


def test_from_keys_collapses_on_constant_array():
    keys = np.full(5000, 42, dtype=np.uint64)
    topo = ShardTopology.from_keys(keys, 8)
    assert topo.n_shards == 1                     # every split collapsed
    assert topo.offsets == (0, 5000)


def test_replica_apportionment_largest_remainder():
    keys = np.arange(4000, dtype=np.uint64)
    topo = ShardTopology.from_keys(keys, 4)
    hot = topo.rebalanced_from_masses([97.0, 1.0, 1.0, 1.0],
                                      total_replicas=8)
    assert sum(hot.replicas) == 8
    assert min(hot.replicas) >= 1                 # floor of one seat
    assert hot.replicas[0] == max(hot.replicas)   # hottest shard wins
    # split points and offsets are untouched: routes stay valid
    assert np.array_equal(hot.split_points, topo.split_points)
    assert hot.offsets == topo.offsets


def test_rebalanced_from_traffic_histogram():
    keys = np.arange(8000, dtype=np.uint64)
    topo = ShardTopology.from_keys(keys, 4)
    flat = topo.rebalanced(np.ones(32), total_replicas=8)
    assert flat.replicas == (2, 2, 2, 2)          # uniform -> even seats
    hist = np.zeros(32)
    hist[:8] = 100.0                              # all mass on shard 0
    skew = topo.rebalanced(hist, total_replicas=8)
    assert skew.replicas[0] == max(skew.replicas) >= 4


# ---------------------------------------------------------------------------
# service parity matrix: routed == broadcast == oracle, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("index", ["rmi", "pgm", "radix_spline"])
@pytest.mark.parametrize("executor", ["sync", "async"])
def test_routed_parity_matrix(datasets, queries, index, executor):
    keys = datasets["amzn"]
    q = queries["amzn"][:2000]
    sp = IndexSpec(index, {})
    bcast = LookupService(keys, LookupServiceConfig(
        spec=sp, max_batch=1024, deadline_ms=0.0, executor=executor))
    routed = LookupService(keys, LookupServiceConfig(
        spec=sp, max_batch=1024, deadline_ms=0.0, executor=executor,
        shards=4))
    try:
        got_b = bcast.lookup(q)
        got_r = routed.lookup(q)
        assert routed.dispatcher.n_shards == 4
        assert np.array_equal(got_r, got_b)
        assert np.array_equal(got_r, _oracle(keys, q))
    finally:
        bcast.stop()
        routed.stop()


def test_routed_parity_pallas_backend(datasets, queries):
    keys = datasets["amzn"]
    q = queries["amzn"][:1000]
    svc = LookupService(keys, LookupServiceConfig(
        spec=IndexSpec("rmi", {}, backend="pallas"),
        max_batch=1024, deadline_ms=0.0, shards=2))
    try:
        assert np.array_equal(svc.lookup(q), _oracle(keys, q))
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# shared routed service for the edge-case / observability block
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def routed_svc(datasets):
    svc = LookupService(datasets["amzn"], LookupServiceConfig(
        spec=IndexSpec("rmi", {}), max_batch=2048, deadline_ms=0.0,
        executor="sync", shards=4))
    yield svc
    svc.stop()


def test_queries_exactly_on_split_points(datasets, routed_svc):
    keys = datasets["amzn"]
    splits = routed_svc.generation.topology.split_points
    q = np.concatenate([splits, splits - 1, splits + 1]).astype(np.uint64)
    assert np.array_equal(routed_svc.lookup(q), _oracle(keys, q))


def test_absent_keys_outside_global_range(datasets, routed_svc):
    keys = datasets["amzn"]
    below = np.array([0, keys[0] - 1], dtype=np.uint64)
    above = np.array([keys[-1] + 1, 2**64 - 1], dtype=np.uint64)
    assert np.array_equal(routed_svc.lookup(below),
                          np.zeros(2, dtype=np.int64))
    assert np.array_equal(routed_svc.lookup(above),
                          np.full(2, keys.size, dtype=np.int64))


def test_batch_entirely_in_one_shard(datasets, routed_svc):
    keys = datasets["amzn"]
    topo = routed_svc.generation.topology
    lo, hi = topo.offsets[2], topo.offsets[3]
    rng = np.random.default_rng(9)
    q = keys[rng.integers(lo, hi, 512)]           # all owned by shard 2
    assert np.array_equal(topo.route(q), np.full(512, 2, dtype=np.int64))
    before = {r["shard"]: r["keys"] for r in routed_svc.metrics.per_shard()}
    assert np.array_equal(routed_svc.lookup(q), _oracle(keys, q))
    after = {r["shard"]: r["keys"] for r in routed_svc.metrics.per_shard()}
    for s in range(4):
        grew = after.get(s, 0) - before.get(s, 0)
        assert grew >= 512 if s == 2 else grew == 0


def test_single_shard_topology_degenerates_bit_exactly(datasets, queries):
    # an EXPLICIT one-shard topology forces the routed machinery
    # (scatter/gather, per-shard health) yet must be bit-identical to
    # plain broadcast dispatch
    keys = datasets["amzn"]
    q = queries["amzn"][:1500]
    bcast = LookupService(keys, LookupServiceConfig(
        spec=IndexSpec("rmi", {}), max_batch=1024, deadline_ms=0.0))
    one = LookupService(keys, LookupServiceConfig(
        spec=IndexSpec("rmi", {}), max_batch=1024, deadline_ms=0.0,
        topology=ShardTopology.single(keys.size)))
    try:
        got_b, got_1 = bcast.lookup(q), one.lookup(q)
        assert np.array_equal(got_1, got_b)
        assert one.metrics.snapshot()["routed_batches"] >= 1   # routed path
        assert bcast.metrics.snapshot()["routed_batches"] == 0
    finally:
        bcast.stop()
        one.stop()


def test_scan_windows_cross_shard_boundaries(datasets, routed_svc):
    # scan windows anchored just below each split must borrow the head
    # of the NEXT shard's range — routed windows == broadcast windows
    keys = datasets["amzn"]
    topo = routed_svc.generation.topology
    anchors = np.array([keys[o - 3] for o in topo.offsets[1:-1]]
                       + [keys[10], keys[-2]], dtype=np.uint64)
    bcast = LookupService(keys, LookupServiceConfig(
        spec=IndexSpec("rmi", {}), max_batch=1024, deadline_ms=0.0))
    try:
        fr = routed_svc.scan(anchors, 64)
        routed_svc.drain()
        fb = bcast.scan(anchors, 64)
        bcast.drain()
        pos_r, win_r = fr.result(timeout=30.0)
        pos_b, win_b = fb.result(timeout=30.0)
        assert np.array_equal(pos_r, pos_b)
        assert np.array_equal(win_r, win_b)
    finally:
        bcast.stop()


def test_hot_swap_routed_generation(datasets):
    keys = datasets["amzn"]
    svc = LookupService(keys, LookupServiceConfig(
        spec=IndexSpec("pgm", {}), max_batch=1024, deadline_ms=0.0,
        shards=3))
    try:
        fresh = np.sort(np.random.default_rng(21).choice(
            2**48, size=30_000, replace=False).astype(np.uint64))
        old_ver = svc.generation.version
        svc.swap_keys(fresh)
        assert svc.generation.version > old_ver
        assert svc.generation.topology.n_keys == fresh.size
        q = np.concatenate([fresh[::100], fresh[:5] + 1]).astype(np.uint64)
        assert np.array_equal(svc.lookup(q), _oracle(fresh, q))
    finally:
        svc.stop()


def test_replica_fanout_and_rebalance(datasets, queries):
    keys = datasets["amzn"]
    q = queries["amzn"][:1500]
    svc = LookupService(keys, LookupServiceConfig(
        spec=IndexSpec("rmi", {}), max_batch=1024, deadline_ms=0.0,
        shards=2, replicas=2))
    try:
        assert svc.generation.topology.replicas == (2, 2)
        assert np.array_equal(svc.lookup(q), _oracle(keys, q))
        reps = svc.rebalance_replicas(total_replicas=6, window_s=60.0)
        assert sum(reps) == 6 and min(reps) >= 1
        # routes and results survive the fan-out change
        assert np.array_equal(svc.lookup(q), _oracle(keys, q))
    finally:
        svc.stop()


def test_per_shard_tuned_specs(datasets, queries):
    keys = datasets["amzn"]
    q = queries["amzn"][:1000]
    svc = LookupService(keys, LookupServiceConfig(
        spec=IndexSpec("rmi", {}), shards=2, max_batch=1024,
        deadline_ms=0.0,
        shard_tuner=Tuner(names=("rmi", "pgm"), max_configs=4)))
    try:
        specs = [g.spec for g in svc.generation.shards]
        assert all(sp is not None for sp in specs)
        assert np.array_equal(svc.lookup(q), _oracle(keys, q))
    finally:
        svc.stop()


def test_mutable_service_rejects_routed_topology(datasets):
    with pytest.raises(ValueError, match="routed"):
        MutableLookupService(datasets["amzn"],
                             MutableLookupServiceConfig(shards=4))


# ---------------------------------------------------------------------------
# per-shard observability + staging contract
# ---------------------------------------------------------------------------
def test_per_shard_metrics_health_and_prometheus(datasets, queries,
                                                 routed_svc):
    from repro.obs.export import MetricsServer, metrics_payload

    keys = datasets["amzn"]
    routed_svc.lookup(queries["amzn"][:2000])     # ensure traffic
    snap = routed_svc.metrics.snapshot()
    assert snap["routed_batches"] >= 1
    assert snap["route_shards"] == 4
    assert snap["route_skew"] >= 1.0
    rows = routed_svc.metrics.per_shard()
    assert {r["shard"] for r in rows} == set(range(4))
    assert all(r["keys"] > 0 for r in rows)
    # merged health snapshot spans the shard group
    h = routed_svc.health_snapshot(window_s=60.0)
    assert h["health_shards"] == 4.0
    # one health record per shard in the registry-facing view, and the
    # shard slices partition the key space exactly
    recs = routed_svc.registry.health_records(60.0)
    by_shard = {r["shard"]: r for r in recs if "shard" in r}
    assert set(by_shard) == set(range(4))
    assert sum(r["n_keys"] for r in by_shard.values()) == keys.size
    # exporter surfaces: /metrics.json per_shard + shard-labelled text
    payload = metrics_payload(routed_svc)
    assert {r["shard"] for r in payload["per_shard"]} == set(range(4))
    server = MetricsServer(routed_svc)
    try:
        text = server.render_prometheus()
        for s in range(4):
            assert f'repro_lookup_shard_keys{{shard="{s}"}}' in text
    finally:
        server._httpd.server_close()


def test_pinned_staging_reuse_steady_state(datasets, routed_svc):
    keys = datasets["amzn"]
    rng = np.random.default_rng(13)
    q = keys[rng.integers(0, keys.size, 300)]     # fixed odd size: padded
    routed_svc.lookup(q)                          # allocate the buckets
    allocs = routed_svc.dispatcher.staging_allocs
    hits = routed_svc.dispatcher.staging_hits
    for _ in range(5):
        routed_svc.lookup(q)
    assert routed_svc.dispatcher.staging_allocs == allocs   # no growth
    assert routed_svc.dispatcher.staging_hits > hits        # reuse


def test_staging_placement_never_aliases_the_buffer(datasets):
    # Regression for a live routed async parity failure: a placed batch
    # must be INDEPENDENT of the pinned staging buffer the moment
    # pad_and_place returns, because the very next batch of the same
    # bucket rewrites that buffer.  Two mechanisms break independence —
    # XLA's CPU zero-copy fast path aliases an owning 64-byte-aligned
    # numpy array outright (so the dispatcher keeps the buffer
    # deliberately misaligned), and the host->device copy is
    # asynchronous (so pad_and_place blocks on the placement).  Without
    # either guard, a whole sub-batch silently answers for the
    # FOLLOWING batch.
    from repro.serve.lookup.dispatch import ShardedDispatcher

    keys = datasets["amzn"]
    d = ShardedDispatcher()
    rng = np.random.default_rng(29)
    q = keys[rng.integers(0, keys.size, 300)]     # odd size: staging path
    qj, p = d.pad_and_place(q)
    assert p > q.size                             # staging buffer used
    assert d._staging[p].ctypes.data % 64 != 0    # zero-copy-proof
    assert qj.is_ready()                          # copy done at return
    # the overwrite-after-return contract: clobbering the staging buffer
    # must not be observable through the already-placed batch
    d._staging[p][:] = 0
    assert np.array_equal(np.asarray(qj)[:q.size], q)


def test_donated_query_buffer_parity(datasets, queries):
    # donation is a no-op on CPU (jax warns) but must never change bits
    keys = datasets["amzn"]
    q = queries["amzn"][:1000]
    svc = LookupService(keys, LookupServiceConfig(
        spec=IndexSpec("rmi", {}), max_batch=1024, deadline_ms=0.0,
        shards=2, donate_queries=True))
    try:
        assert np.array_equal(svc.lookup(q), _oracle(keys, q))
        assert np.array_equal(svc.lookup(q), _oracle(keys, q))  # reuse
    finally:
        svc.stop()


def test_routed_shards_sample_key_is_first_shard_key(datasets, routed_svc):
    """Each shard generation's warm-up sample key is the first key of its
    own range, and every lane context of the shard carries it."""
    keys = datasets["amzn"]
    gen = routed_svc.generation
    offs = gen.topology.offsets
    rctx = routed_svc._routed_context(gen)
    for s, sgen in enumerate(gen.shards):
        assert sgen.sample_key == int(keys[offs[s]])
        assert {c.sample_key for c in rctx.lane_ctxs[s]} == {sgen.sample_key}
