"""Async-executor suite (DESIGN.md §13): the continuous-batching engine
is pinned against the synchronous path and the replay oracle.

Four layers of assurance, strongest first:

  parity     every index × backend cell produces BIT-IDENTICAL results
             under executor="async" and executor="sync" — positions and
             scan windows, through real threads;
  replay     a mixed read/insert/range trace (compactions forced
             mid-trace) replayed on the async mutable service matches
             `oracle_scan_replay` bit-for-bit — the end-to-end
             linearization invariant;
  stress     N concurrent client threads against one started service:
             exactness (immutable), linearization brackets (mutable),
             per-client FIFO completion, no unresolved futures, a warm
             cache actually hitting;
  faults     a dispatch-time failure, a completion-time failure, and an
             insert-apply failure each fail ONLY their own batch's
             futures with the original exception and leave the slot ring
             clean; hot-swap racing an in-flight slot completes against
             the generation the slot pinned; `result(timeout)` expiry
             orphans nothing; `stop()` with a straggler joins cleanly.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import base
from repro.data import sosd
from repro.serve.lookup import (AsyncExecutor, ExecutableCache,
                                LookupService, LookupServiceConfig,
                                MutableLookupService,
                                MutableLookupServiceConfig)
from repro.workloads import replay as replay_mod
from repro.workloads.workload import OP_INSERT, make_workload

UINT64_MAX = np.uint64(0xFFFFFFFFFFFFFFFF)


# ---------------------------------------------------------------------------
# shared data (module-scoped: every test reuses one build of the cell)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cell():
    keys = sosd.generate("amzn", 20_000, seed=3)
    q = sosd.make_queries(keys, 2_000, seed=5, present_frac=0.6)
    return keys, q, base.lower_bound_oracle(keys, q)


def _scan_oracle(keys, pos, m):
    w = np.full((pos.size, m), UINT64_MAX, dtype=np.uint64)
    for i, p in enumerate(pos):
        seg = keys[p:p + m]
        w[i, :seg.size] = seg
    return w


def _svc(keys, executor, **over):
    kw = dict(index="rmi", hyper=dict(branching=512), max_batch=256,
              deadline_ms=1.0, executor=executor)
    kw.update(over)
    return LookupService(keys, LookupServiceConfig(**kw))


# ---------------------------------------------------------------------------
# parity: async ≡ sync, bit for bit, across the index × backend matrix
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("index,hyper,backend", [
    ("rmi", dict(branching=512), "jnp"),
    ("rmi", dict(branching=512), "pallas"),
    ("pgm", dict(eps=32), "jnp"),
    ("radix_spline", dict(eps=32, radix_bits=12), "jnp"),
])
def test_async_matches_sync_bit_identical(cell, index, hyper, backend):
    keys, q, lb = cell
    outs = {}
    for executor in ("sync", "async"):
        svc = _svc(keys, executor, index=index, hyper=hyper,
                   backend=backend, warm_scan_lengths=(16,))
        with svc:
            reads = [svc.submit(q[i:i + 97]) for i in range(0, q.size, 97)]
            scans = [svc.scan(q[i:i + 50], 16) for i in range(0, 200, 50)]
            outs[executor] = (
                np.concatenate([f.result(60.0) for f in reads]),
                [f.result(60.0) for f in scans])
    pos_s, scans_s = outs["sync"]
    pos_a, scans_a = outs["async"]
    np.testing.assert_array_equal(pos_a, pos_s)
    np.testing.assert_array_equal(pos_s, lb)
    for (ps, ws), (pa, wa) in zip(scans_s, scans_a):
        np.testing.assert_array_equal(pa, ps)
        np.testing.assert_array_equal(wa, ws)
    w0 = scans_a[0][1]
    np.testing.assert_array_equal(w0, _scan_oracle(keys, lb[:50], 16))


def test_async_replay_matches_oracle_with_compactions(cell):
    """Mixed trace, async executor, compactions racing the slot ring:
    positions, admitted flags, AND scan windows equal the oracle's."""
    keys, _, _ = cell
    wl = make_workload(keys, 600,
                       mix={"read": 0.5, "insert": 0.3, "range": 0.2},
                       seed=17, range_len=16)
    want, want_win = replay_mod.oracle_scan_replay(keys, wl)
    svc = MutableLookupService(keys, MutableLookupServiceConfig(
        index="pgm", hyper=dict(eps=32), max_batch=256, deadline_ms=1.0,
        executor="async", compact_threshold=512, warm_scan_lengths=(16,)))
    with svc:
        got, got_win = replay_mod.replay_on_service(
            wl, svc, chunk=48, compact_every=200, scan_ranges=True)
        # every future resolved => every insert applied; fold whatever
        # delta remains (an EMPTY delta here means a compaction already
        # fired mid-trace) so the swap path is exercised either way
        assert (want[wl.ops == OP_INSERT] == 1).any()
        if svc.mindex.delta_count:
            svc.force_compact()
        assert svc.metrics.snapshot()["compactions"] >= 1
        # post-compaction reads stay exact against the merged oracle
        merged = np.union1d(keys, wl.keys[(wl.ops == OP_INSERT)
                                          & (want == 1)])
        probe = wl.keys[wl.ops != OP_INSERT][:300]
        np.testing.assert_array_equal(
            svc.lookup(probe, timeout=60.0),
            base.lower_bound_oracle(merged, probe))
    np.testing.assert_array_equal(got, want)
    assert set(got_win) == set(want_win)
    for i in want_win:
        np.testing.assert_array_equal(got_win[i], want_win[i])


# ---------------------------------------------------------------------------
# stress: concurrent clients against one started service
# ---------------------------------------------------------------------------
def test_stress_concurrent_reads_and_scans_exact(cell):
    keys, q, lb = cell
    svc = _svc(keys, "async", warm_scan_lengths=(8,))
    n_threads, errs = 6, []

    def client(t):
        try:
            rng = np.random.default_rng(t)
            for _ in range(30):
                lo = int(rng.integers(0, q.size - 64))
                n = int(rng.integers(1, 64))
                if t % 3 == 0:
                    f = svc.scan(q[lo:lo + n], 8)
                    pos, win = f.result(60.0)
                    np.testing.assert_array_equal(pos, lb[lo:lo + n])
                    np.testing.assert_array_equal(
                        win, _scan_oracle(keys, lb[lo:lo + n], 8))
                else:
                    f = svc.submit(q[lo:lo + n])
                    np.testing.assert_array_equal(
                        f.result(60.0), lb[lo:lo + n])
        except BaseException as e:   # noqa: BLE001 — surface in main thread
            errs.append(e)

    with svc:
        ts = [threading.Thread(target=client, args=(t,))
              for t in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    assert not errs, errs
    snap = svc.metrics.snapshot()
    # the §13 observability contract: a warm cache HITS under steady
    # traffic, and the decomposed latencies are populated
    assert snap["cache_hit_rate"] > 0.0
    assert snap["warm_compiles"] > 0
    assert snap["p99_request_ms"] > 0.0
    assert snap["p99_queue_ms"] > 0.0
    assert svc._async._inflight == 0
    assert svc._async._ring.empty()


def test_stress_mutable_concurrent_writers_bracketed(cell):
    """Readers race two disjoint insert streams: every read result is
    bracketed by LB(base) <= got <= LB(base ∪ all inserts) (inserts only
    ever shift LB up), every insert is admitted exactly once, and no
    future is left pending."""
    keys, q, _ = cell
    half = keys[::2].copy()
    fresh = np.setdiff1d(keys[1::2], half)[:2_000]
    lo_lb = base.lower_bound_oracle(half, q)
    hi_lb = base.lower_bound_oracle(np.union1d(half, fresh), q)
    svc = MutableLookupService(half, MutableLookupServiceConfig(
        index="pgm", hyper=dict(eps=32), max_batch=256, deadline_ms=1.0,
        executor="async", compact_threshold=768))
    errs, admitted = [], []

    def writer(lo):
        try:
            part = fresh[lo::2]
            futs = [svc.insert(part[i:i + 100])
                    for i in range(0, part.size, 100)]
            admitted.append(sum(int(f.result(60.0).sum()) for f in futs))
        except BaseException as e:   # noqa: BLE001
            errs.append(e)

    def reader(t):
        try:
            rng = np.random.default_rng(100 + t)
            for _ in range(25):
                lo = int(rng.integers(0, q.size - 64))
                n = int(rng.integers(1, 64))
                got = svc.submit(q[lo:lo + n]).result(60.0)
                assert np.all(lo_lb[lo:lo + n] <= got)
                assert np.all(got <= hi_lb[lo:lo + n])
        except BaseException as e:   # noqa: BLE001
            errs.append(e)

    with svc:
        ts = ([threading.Thread(target=writer, args=(w,)) for w in range(2)]
              + [threading.Thread(target=reader, args=(t,))
                 for t in range(3)])
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    assert not errs, errs
    assert sum(admitted) == fresh.size          # set semantics, no loss
    merged = np.union1d(half, fresh)
    np.testing.assert_array_equal(svc.lookup(q[:500]),
                                  base.lower_bound_oracle(merged, q[:500]))


def test_fifo_completion_per_client(cell):
    """Completion order is admission order: in ANY snapshot, the done
    set is a prefix.  Reading newest -> oldest with completion racing,
    a done future must never be followed by a pending older one."""
    keys, q, _ = cell
    svc = _svc(keys, "async", max_batch=64)
    with svc:
        # slow the (already warmed) read executable a little so
        # completion is observably gradual
        bucket = svc.dispatcher.padded_size(64)
        ckey = ((svc.generation.version,), "read", 0, bucket)
        real = svc.exec_cache._exes[ckey]
        svc.exec_cache._exes[ckey] = (
            lambda *a: (time.sleep(0.003), real(*a))[1])
        futs = [svc.submit(q[i * 32:(i + 1) * 32]) for i in range(40)]
        deadline = time.perf_counter() + 60.0
        while not futs[-1].done():
            saw_done = False
            for f in reversed(futs):
                d = f.done()
                assert not (saw_done and not d), "per-client FIFO violated"
                saw_done = saw_done or d
            assert time.perf_counter() < deadline
    assert all(f.done() for f in futs)


def test_double_buffering_overlaps_inflight_slots(cell):
    """With completion artificially slow, the dispatch thread keeps
    launching: observed in-flight slot depth must exceed one (the whole
    point of the ring) and never exceed the configured bound."""
    keys, q, lb = cell
    svc = _svc(keys, "async", max_batch=64, slots=3)
    real_finalize = svc.dispatcher.finalize
    svc.dispatcher.finalize = (
        lambda out, m, **kw: (time.sleep(0.02),
                              real_finalize(out, m, **kw))[1])
    with svc:
        futs = [svc.submit(q[i * 64:(i + 1) * 64]) for i in range(12)]
        got = np.concatenate([f.result(60.0) for f in futs])
    np.testing.assert_array_equal(got, lb[:12 * 64])
    snap = svc.metrics.snapshot()
    assert snap["max_inflight_slots"] >= 2
    # bound = ring capacity + one slot mid-completion (popped) + one
    # launch blocked entering the full ring: in-flight memory is bounded
    assert snap["max_inflight_slots"] <= 3 + 2
    assert snap["mean_inflight_slots"] > 0.0


# ---------------------------------------------------------------------------
# drain/stop: nothing admitted is ever left unresolved
# ---------------------------------------------------------------------------
def test_inline_drain_resolves_everything_and_empties_ring(cell):
    """No threads at all: drain() on a never-started async service
    launches AND completes every admission — including past the slot
    bound (more batches in flight than slots forces the inline
    oldest-first completion path)."""
    keys, q, lb = cell
    svc = _svc(keys, "async", max_batch=64, slots=2)
    futs = [svc.submit(q[i * 64:(i + 1) * 64]) for i in range(10)]
    svc.drain()
    assert all(f.done() for f in futs)
    got = np.concatenate([f.result(1.0) for f in futs])
    np.testing.assert_array_equal(got, lb[:640])
    assert svc._async._inflight == 0
    assert svc._async._ring.empty()


def test_stop_resolves_everything_admitted(cell):
    keys, q, lb = cell
    svc = _svc(keys, "async", max_batch=128)
    svc.start()
    futs = [svc.submit(q[i * 50:(i + 1) * 50]) for i in range(30)]
    svc.stop()                      # immediate: no settle wait first
    assert all(f.done() for f in futs)
    got = np.concatenate([f.result(1.0) for f in futs])
    np.testing.assert_array_equal(got, lb[:1500])
    # the service stays usable synchronously after stop()
    np.testing.assert_array_equal(svc.lookup(q[:40]), lb[:40])


def test_result_timeout_orphans_nothing(cell):
    """A client timing out on `result` must not orphan the request:
    the executor still resolves it, and drain() does not deadlock."""
    keys, q, lb = cell
    svc = _svc(keys, "async")
    fut = svc.submit(q[:64])        # not started: nothing will flush yet
    with pytest.raises(TimeoutError):
        fut.result(timeout=0.01)
    svc.drain()                     # must terminate, resolving the future
    np.testing.assert_array_equal(fut.result(1.0), lb[:64])


def test_stop_with_straggler_joins_cleanly(cell):
    """A slot stuck in a slow executable when stop() lands: the join
    must complete in bounded time WITH the straggler's future resolved
    correctly (completion loop runs the ring dry before the sentinel)."""
    keys, q, lb = cell
    svc = _svc(keys, "async", max_batch=64)
    svc.start()
    bucket = svc.dispatcher.padded_size(64)
    ckey = ((svc.generation.version,), "read", 0, bucket)
    real = svc.exec_cache._exes[ckey]
    svc.exec_cache._exes[ckey] = (
        lambda *a: (time.sleep(0.5), real(*a))[1])
    fut = svc.submit(q[:64])
    t0 = time.perf_counter()
    svc.stop()
    assert time.perf_counter() - t0 < 30.0
    np.testing.assert_array_equal(fut.result(1.0), lb[:64])


# ---------------------------------------------------------------------------
# executable cache: hits, warm accounting, invalidation-on-swap
# ---------------------------------------------------------------------------
def test_cache_hits_after_warmup_no_steady_state_misses(cell):
    """After `start()`'s warm-up, fixed-shape traffic NEVER misses:
    every batch is a hit against a pre-compiled executable, and warm-up
    itself is accounted separately (it must not inflate the hit rate)."""
    keys, q, lb = cell
    svc = _svc(keys, "async", max_batch=128)
    with svc:
        futs = [svc.submit(q[i * 128:(i + 1) * 128]) for i in range(8)]
        for f in futs:
            f.result(60.0)
    snap = svc.metrics.snapshot()
    assert snap["warm_compiles"] > 0
    assert snap["cache_misses"] == 0
    assert snap["cache_hits"] >= 8
    assert snap["cache_hit_rate"] == 1.0


def test_hot_swap_invalidates_cache_and_rewarms(cell):
    """Publish -> stale generations' executables evicted (only entries
    keyed by the new version survive) -> traffic against the new key
    set is exact and hits again once re-warmed."""
    keys, q, _ = cell
    svc = _svc(keys, "async", max_batch=128)
    with svc:
        svc.lookup(q[:128], timeout=60.0)
        assert len(svc.exec_cache) > 0
        new_keys = keys[::2].copy()
        gen = svc.swap_keys(new_keys)
        with svc.exec_cache._mu:
            assert all(k[0][0] == gen.version
                       for k in svc.exec_cache._exes)
        lb2 = base.lower_bound_oracle(new_keys, q[:300])
        np.testing.assert_array_equal(svc.lookup(q[:300], timeout=60.0), lb2)


def test_hot_swap_races_inflight_slot_old_generation_wins(cell):
    """A slot launched before the swap completes against the generation
    it pinned — the swap is invisible to in-flight work (§9.3 semantics
    carried over to the ring)."""
    keys, q, lb = cell
    svc = _svc(keys, "async")
    fut = svc.submit(q[:100])
    svc._async._drain_launches()        # launched against the OLD plan
    new_keys = keys[::4].copy()
    svc.swap_keys(new_keys)             # swap while the slot is in flight
    svc._async._complete_ring_inline()
    np.testing.assert_array_equal(fut.result(1.0), lb[:100])   # old gen
    # and the NEXT batch sees the new generation
    lb_new = base.lower_bound_oracle(new_keys, q[:100])
    np.testing.assert_array_equal(svc.lookup(q[:100], timeout=60.0), lb_new)


def test_executable_cache_unit_semantics():
    cache = ExecutableCache()
    ctx_key = (7,)
    # duck-typed: only .key/.bind/.instrumented are read
    ctx = type("C", (), {})()
    ctx.key, ctx.bind, ctx.instrumented = ctx_key, (), False
    fn = lambda q: q                # no .lower: stored as-is  # noqa: E731
    got = cache.get(ctx, "read", 0, 128, lambda: fn, dispatcher=None,
                    warm=True)
    assert got is fn
    assert cache.counters() == (0, 0)       # warm never counts hit/miss
    assert cache.warm_compiles == 1
    assert cache.get(ctx, "read", 0, 128, lambda: fn, None) is fn
    assert cache.counters() == (1, 0)       # serving hit
    cache.get(ctx, "read", 0, 256, lambda: fn, None)
    assert cache.counters() == (1, 1)       # new bucket: serving miss
    ctx2 = type("C", (), {})()
    ctx2.key, ctx2.bind, ctx2.instrumented = (8,), (), False
    cache.get(ctx2, "read", 0, 128, lambda: fn, None)
    assert len(cache) == 3
    assert cache.invalidate(keep_version=8) == 2    # both v7 entries die
    assert len(cache) == 1
    assert cache.invalidate() == 1                  # full clear
    assert cache.hit_rate == pytest.approx(1 / 3)


def test_async_executor_requires_double_buffering():
    with pytest.raises(ValueError, match="slots"):
        AsyncExecutor(service=None, slots=1)
    with pytest.raises(ValueError, match="executor"):
        LookupService(np.arange(1, 100, dtype=np.uint64),
                      LookupServiceConfig(executor="turbo"))


# ---------------------------------------------------------------------------
# fault injection: failures are request-scoped, never engine-scoped
# ---------------------------------------------------------------------------
class Boom(RuntimeError):
    pass


def test_launch_failure_fails_only_that_batch(cell):
    """An executable-resolution failure mid-dispatch fails exactly that
    batch's futures with the ORIGINAL exception; the ring stays clean
    and the very next batch succeeds."""
    keys, q, lb = cell
    svc = _svc(keys, "async", max_batch=64)
    with svc:
        boom = Boom("resolution exploded")
        real_get = svc.exec_cache.get
        fired = threading.Event()

        def poisoned(ctx, kind, aux, bucket, make_fn, dispatcher,
                     warm=False):
            if not warm and not fired.is_set():
                fired.set()
                raise boom
            return real_get(ctx, kind, aux, bucket, make_fn, dispatcher,
                            warm=warm)

        svc.exec_cache.get = poisoned
        bad = svc.submit(q[:64])
        with pytest.raises(Boom) as ei:
            bad.result(60.0)
        assert ei.value is boom                 # original exception object
        good = svc.submit(q[64:128])
        np.testing.assert_array_equal(good.result(60.0), lb[64:128])
    assert svc._async._inflight == 0
    assert svc._async._ring.empty()


def test_completion_failure_fails_only_that_slot(cell):
    """A device-side failure surfacing at finalize fails that slot's
    futures; the completion loop keeps serving later slots."""
    keys, q, lb = cell
    svc = _svc(keys, "async", max_batch=64)
    with svc:
        bucket = svc.dispatcher.padded_size(64)
        ckey = ((svc.generation.version,), "read", 0, bucket)
        real = svc.exec_cache._exes[ckey]
        svc.exec_cache._exes[ckey] = lambda *a: None   # finalize will choke
        bad = svc.submit(q[:64])
        with pytest.raises(BaseException):
            bad.result(60.0)
        svc.exec_cache._exes[ckey] = real
        good = svc.submit(q[:64])
        np.testing.assert_array_equal(good.result(60.0), lb[:64])


def test_insert_failure_fails_only_that_run(cell):
    """An insert-apply failure (delta layer raising) fails the insert
    run's futures with the original exception; reads before and after
    keep completing, and a later insert succeeds."""
    keys, q, _ = cell
    half = keys[::2].copy()
    lb_half = base.lower_bound_oracle(half, q[:64])
    svc = MutableLookupService(half, MutableLookupServiceConfig(
        index="pgm", hyper=dict(eps=32), max_batch=128, deadline_ms=1.0,
        executor="async", auto_compact=False))
    fresh = np.setdiff1d(keys[1::2], half)[:50]
    with svc:
        boom = Boom("delta exploded")
        real_insert = svc.mindex.insert
        fired = threading.Event()

        def poisoned(ks):
            if not fired.is_set():
                fired.set()
                raise boom
            return real_insert(ks)

        svc.mindex.insert = poisoned
        r0 = svc.submit(q[:64])
        bad = svc.insert(fresh)
        r1 = svc.submit(q[:64])
        np.testing.assert_array_equal(r0.result(60.0), lb_half)
        with pytest.raises(Boom) as ei:
            bad.result(60.0)
        assert ei.value is boom
        np.testing.assert_array_equal(r1.result(60.0), lb_half)
        ok = svc.insert(fresh)
        assert int(ok.result(60.0).sum()) == fresh.size
    merged = np.union1d(half, fresh)
    np.testing.assert_array_equal(
        svc.lookup(q[:64]), base.lower_bound_oracle(merged, q[:64]))


# ---------------------------------------------------------------------------
# tracing: one span of each phase per device batch, joined by `batch`
# ---------------------------------------------------------------------------
_PHASES = ("pin", "gather", "launch", "finalize", "resolve")
_CHILDREN = {"pad_place": "launch", "enqueue": "launch",
             "device_wait": "finalize", "copy_back": "finalize",
             "stats_copy": "finalize"}


@pytest.mark.parametrize("mutable", [False, True],
                         ids=["LookupService", "MutableLookupService"])
def test_traced_batches_have_one_span_per_phase(cell, mutable):
    """Every launched device batch records exactly one span of each
    dispatch and completion phase, all carrying the batch's sequence
    number; each child span lies inside its parent on the same thread."""
    keys, q, lb = cell
    kw = dict(index="pgm", hyper=dict(eps=32), max_batch=128,
              deadline_ms=1.0, executor="async", trace=True, health=True)
    svc = (MutableLookupService(keys, MutableLookupServiceConfig(
        auto_compact=False, **kw)) if mutable
        else LookupService(keys, LookupServiceConfig(**kw)))
    with svc:
        futs = [svc.submit(q[i * 64:(i + 1) * 64]) for i in range(12)]
        got = np.concatenate([f.result(60.0) for f in futs])
    np.testing.assert_array_equal(got, lb[:12 * 64])

    spans = [s for s in svc.recorder.spans()
             if s.name in _PHASES or s.name in _CHILDREN]
    by = {}
    for s in spans:
        by.setdefault(s.args["batch"], {}).setdefault(s.name, []).append(s)
    launched = {s.args["batch"] for s in spans if s.name == "launch"}
    assert launched and set(by) == launched
    rids = []
    for b, named in by.items():
        for name in _PHASES:
            assert len(named.get(name, ())) == 1, (b, name)
        launch, fin = named["launch"][0], named["finalize"][0]
        assert fin.args["rid_first"] == launch.args["rid_first"]
        assert fin.args["rid_last"] == launch.args["rid_last"]
        rids.extend(range(launch.args["rid_first"],
                          launch.args["rid_last"] + 1))
        # dispatch phases on one thread, in order; completion on another
        pin, gather = named["pin"][0], named["gather"][0]
        assert pin.tid == gather.tid == launch.tid != fin.tid
        assert pin.t0 + pin.dur <= gather.t0 <= launch.t0
        assert fin.t0 + fin.dur <= named["resolve"][0].t0
        for child, parent in _CHILDREN.items():
            (c,) = named[child]
            (p,) = named[parent]
            assert c.tid == p.tid
            assert p.t0 <= c.t0 and c.t0 + c.dur <= p.t0 + p.dur
    assert sorted(rids) == sorted(f.rid for f in futs)


# ---------------------------------------------------------------------------
# pinning: the warm-up sample key is a constant of the generation
# ---------------------------------------------------------------------------
def _svc_of(keys, mutable, **over):
    kw = dict(index="pgm", hyper=dict(eps=32), max_batch=128,
              deadline_ms=1.0, executor="async")
    kw.update(over)
    if mutable:
        return MutableLookupService(keys, MutableLookupServiceConfig(
            auto_compact=False, **kw))
    return LookupService(keys, LookupServiceConfig(**kw))


class _UnreadableKeys:
    """Stands in for a generation's device key array: any read of it
    fails the test."""

    def __getitem__(self, _):
        raise AssertionError("the pin read the generation's key array")

    def __array__(self, *args, **kwargs):
        raise AssertionError("the pin copied the generation's key array")


@pytest.mark.parametrize("mutable", [False, True],
                         ids=["LookupService", "MutableLookupService"])
def test_pin_reads_no_key_data(cell, mutable):
    """Once the operands are placed, pinning a batch's context reads
    nothing of the generation's key array: no device slice, no copy to
    the host — and serving stays exact without it."""
    keys, q, lb = cell
    svc = _svc_of(keys, mutable)
    with svc:
        gen = svc.generation
        real = gen.data
        object.__setattr__(gen, "data", _UnreadableKeys())
        try:
            ctxs = [svc._pinned_context(seq) for seq in range(64)]
            got = svc.lookup(q[:300], timeout=60.0)
        finally:
            object.__setattr__(gen, "data", real)
    assert {c.key[0] for c in ctxs} == {gen.version}
    assert {c.sample_key for c in ctxs} == {int(keys[0])}
    np.testing.assert_array_equal(got, lb[:300])


@pytest.mark.parametrize("mutable", [False, True],
                         ids=["LookupService", "MutableLookupService"])
def test_sample_key_is_first_key_across_hot_swaps(cell, mutable):
    """The sample key is the generation's first key, the same on every
    hot-swap of the same keys, and warm-up compiles one executable per
    bucket for each generation."""
    keys, _, _ = cell
    svc = _svc_of(keys, mutable)
    with svc:
        n_warm = svc.exec_cache.warm_compiles
        assert n_warm == len(svc._resolved_warm_buckets()) > 0
        versions = set()
        for swap in range(3):
            if swap:
                svc.swap_keys(keys)
                svc.warm_wait(60.0)
            gen = svc.generation
            ctx = svc._pinned_context(swap)
            assert ctx.key[0] == gen.version not in versions
            versions.add(gen.version)
            assert ctx.sample_key == gen.sample_key == int(keys[0])
        assert svc.exec_cache.warm_compiles == 3 * n_warm
