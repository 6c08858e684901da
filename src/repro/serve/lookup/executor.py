"""Continuous-batching async executor + executable cache (DESIGN.md §13).

The synchronous dispatch path (`LookupService._dispatch_once`) is serial
batch-at-a-time: take a batch, trace/compile on first contact, block on
the device, complete futures, only then admit the next batch.  The p99
of that loop is bounded by Python dispatch and first-touch compilation,
not by kernel time (`benchmarks/results/serve_throughput.json`).  This
module rebuilds the path the way LLM inference servers do:

  executable cache   `ExecutableCache` maps ``(context key, kind, aux,
                     pow2 batch bucket)`` to a ready-to-run executable —
                     AOT-lowered (`jitted.lower(...).compile()`) against
                     the dispatcher's padded bucket shape and batch
                     sharding where the callable supports it, the primed
                     jit wrapper otherwise.  Steady-state dispatch never
                     re-traces or re-compiles; warm-up primes the common
                     buckets at `start()` and again after every hot-swap
                     (`IndexRegistry` publish subscription), off the
                     dispatch thread.

  double buffering   the DISPATCH thread takes a batch, pins its
                     context, pads, places, and LAUNCHES the device step
                     without blocking on it (jax async dispatch); the
                     COMPLETION thread blocks on device results and
                     resolves futures.  Admission and host-side
                     completion of batch N overlap the in-flight device
                     execution of batch N+1.

  slot ring          launched batches ride a bounded FIFO ring of
                     in-flight slots.  A straggler (scan run, cold
                     bucket) occupies one slot; admission (`submit`)
                     never blocks, and the dispatch thread only waits
                     when the whole ring is full — bounded in-flight
                     memory, no unbounded queue growth.  Completing
                     slots strictly in ring order preserves the global
                     admission order, hence per-client FIFO completion.

Every result is bit-identical to the synchronous path: both execute the
same plan-compiled programs over the same padded buckets, and positions/
windows are exact integers (pinned across the index × backend matrix by
tests/test_serve_executor.py).
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from repro.obs.trace import maybe_span
from repro.serve.lookup.dispatch import RoutedContext

__all__ = ["AsyncContext", "AsyncExecutor", "ExecutableCache", "WorkItem"]


@dataclasses.dataclass(frozen=True)
class AsyncContext:
    """One pinned lookup context, executable-cache addressable.

    ``key`` namespaces the cache: everything the compiled program
    depends on beyond operand shapes — the generation version (and, for
    merged mutable views, the padded delta length, a compile-shape
    axis).  ``bind`` holds the device operands appended after the query
    batch: the plan's operand pytree (key array + index state, placed
    for the dispatcher), preceded by the padded delta for merged
    lookups.  They are ARGUMENTS, not closure constants: the compiled
    program embeds no key data, and a new delta or a new generation of
    the same shape runs the same program.
    """

    key: Tuple                 # hashable; key[0] is the generation version
    read_fn: Callable          # (q, *bind) -> positions
    scan_fn: Callable          # m -> ((q, *bind) -> (positions, window))
    bind: Tuple = ()           # device operands appended after q (pytrees)
    #: a valid key for warm-up dummy batches: the pinned generation's
    #: `Generation.sample_key`, fixed when it was made, so a pin reads
    #: no device memory
    sample_key: int = 1
    #: Health telemetry (DESIGN.md §15): when set, ``read_fn`` is the
    #: plan's instrumented executable ``(q, n_valid, *bind) -> (pos,
    #: stats)`` — reads pass the real batch size as a dynamic int32
    #: scalar and completion strips the stats off for the monitor.
    instrumented: bool = False


@dataclasses.dataclass
class WorkItem:
    """One dispatchable unit: a same-kind request group + how to run it."""

    kind: str                           # "read" | "scan" | "insert"
    group: List                         # PendingRequests, admission order
    ctx: Optional[AsyncContext] = None  # device kinds only
    aux: int = 0                        # scan length for kind="scan"
    apply_fn: Optional[Callable] = None  # host op (inserts): group -> array


@dataclasses.dataclass
class _Slot:
    """One in-flight ring entry.  Exactly one of (out, host, error) is
    meaningful: a launched device computation, a host-side result that
    is already final (inserts), or a launch failure to propagate."""

    group: List
    kind: str
    out: Any = None              # in-flight device output (async dispatch)
    m: int = 0                   # real key count (pre-padding)
    padded: int = 0
    host: Any = None             # host-ready result (inserts)
    error: Optional[BaseException] = None
    t_submit_oldest: float = 0.0
    t_launch: float = 0.0
    is_insert: bool = False
    version: int = -1            # generation the stats (if any) belong to
    instrumented: bool = False   # out is (payload, packed health stats)
    routed: bool = False         # out is a dispatch._RoutedHandle
    batch: int = 0               # the executor's sequence number of the
    #                              taken batch: every span of it carries it


_STOP = object()


class ExecutableCache:
    """(context key, kind, aux, bucket) -> ready-to-run executable.

    The cache makes compilation an explicit, observable event instead of
    a silent p99 outlier: a **miss** builds the executable (AOT when the
    callable is a jitted function, fallback to the callable itself — the
    plan layer's jit wrappers keep their own shape-keyed trace cache, so
    a stored wrapper never re-traces for a bucket it has seen); a
    **hit** dispatches a pre-compiled program with only data operands
    changing.  Counters feed `ServiceMetrics` so a zero steady-state hit
    rate (per-batch recompiles) is a test failure, not a latency
    mystery.  `invalidate(keep_version=...)` evicts every entry of older
    generations on hot-swap; in-flight slots hold direct references to
    their executables, so eviction never races a running batch.
    """

    def __init__(self, metrics=None, recorder=None):
        self._mu = threading.Lock()
        self._exes: dict = {}
        self.hits = 0
        self.misses = 0
        self.warm_compiles = 0
        self.metrics = metrics
        #: optional `repro.obs.trace.SpanRecorder`: every build becomes
        #: a "compile" span — the p99 outlier the cache exists to hide
        #: is visible (and attributable) in the exported trace.
        self.recorder = recorder

    # -- stats -----------------------------------------------------------
    def counters(self) -> Tuple[int, int]:
        with self._mu:
            return self.hits, self.misses

    @property
    def hit_rate(self) -> float:
        with self._mu:
            n = self.hits + self.misses
            return self.hits / n if n else 0.0

    def __len__(self) -> int:
        with self._mu:
            return len(self._exes)

    # -- build/get -------------------------------------------------------
    @staticmethod
    def _build(fn, bucket: int, bind: Tuple, dispatcher,
               instrumented: bool = False):
        """AOT-lower ``fn`` for the padded bucket (batch-sharded query +
        bind operands where they are placed) when it supports `.lower`;
        otherwise return the callable unchanged (injected plain
        callables just run).  Instrumented executables take the real
        batch size as a dynamic int32 scalar between the query and the
        bind operands — ONE compiled program per bucket, not one per
        occupancy.  A compile failure raises: it fails the batch (or the
        warm-up) that asked for it, and is never served around."""
        import jax
        import jax.numpy as jnp

        lower = getattr(fn, "lower", None)
        if lower is None:
            return fn

        def sds(x):
            sharding = getattr(x, "sharding", None)
            if getattr(x, "committed", True) is False:
                sharding = None     # uncommitted: follows the query
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

        sds_q = jax.ShapeDtypeStruct(
            (bucket,), jnp.uint64,
            sharding=dispatcher.query_sharding(bucket))
        sds_args = ([jax.ShapeDtypeStruct((), jnp.int32)]
                    if instrumented else [])
        sds_args += [jax.tree.map(sds, b) for b in bind]
        return lower(sds_q, *sds_args).compile()

    def get(self, ctx: AsyncContext, kind: str, aux: int, bucket: int,
            make_fn: Callable, dispatcher, warm: bool = False):
        """Return the executable for one cell, building it on miss.

        ``make_fn`` produces the source callable (``gen.fn``, a merged
        fn, a scan executable); it only runs on a miss.  ``warm=True``
        counts the build as a warm-up compile instead of a serving-path
        miss, so steady-state hit-rate assertions are not diluted by
        deliberate priming.
        """
        key = (ctx.key, kind, int(aux), int(bucket))
        with self._mu:
            exe = self._exes.get(key)
            hit = exe is not None
            # warm-up traffic never counts toward serving hit/miss: the
            # steady-state hit-rate assertion must measure real batches
            if warm:
                self.warm_compiles += 0 if hit else 1
            elif hit:
                self.hits += 1
            else:
                self.misses += 1
        if exe is None:
            with maybe_span(self.recorder, "compile", cat="compile",
                            kind=kind, aux=int(aux), bucket=int(bucket),
                            version=ctx.key[0], warm=bool(warm)):
                exe = self._build(
                    make_fn(), bucket, ctx.bind, dispatcher,
                    instrumented=ctx.instrumented and kind == "read")
            with self._mu:
                self._exes[key] = exe
        if self.metrics is not None:
            self.metrics.note_cache(hit=hit, warm=warm)
        return exe

    def invalidate(self, keep_version=None) -> int:
        """Evict entries; with ``keep_version`` set, only entries whose
        context belongs to another generation go (hot-swap policy: the
        new generation's warm-up repopulates, old executables die).
        Accepts a single version or an iterable of versions to keep —
        a routed publish keeps the RoutedGeneration's version AND every
        per-shard generation version (lane contexts key on those)."""
        with self._mu:
            if keep_version is None:
                n = len(self._exes)
                self._exes.clear()
                return n
            keep = (set(keep_version)
                    if isinstance(keep_version, (set, frozenset, tuple,
                                                 list))
                    else {keep_version})
            stale = [k for k in self._exes if k[0][0] not in keep]
            for k in stale:
                del self._exes[k]
            return len(stale)

    def warmup(self, ctx: AsyncContext, buckets, dispatcher,
               scan_lengths=()) -> int:
        """Prime read (and optionally scan) executables for ``buckets``
        and run one dummy batch through each — after this, the first
        real batch of a warmed bucket is a cache hit with no trace, no
        compile, no first-touch initialization.  Runs off the dispatch
        thread (service `start()`, or the post-publish warm thread)."""
        import jax

        n = 0
        cells = [("read", 0, lambda: ctx.read_fn)]
        cells += [("scan", int(m), (lambda m=m: ctx.scan_fn(int(m))))
                  for m in scan_lengths]
        host_dummy = {int(b): np.full(int(b), ctx.sample_key, np.uint64)
                      for b in buckets}
        for bucket in buckets:
            for kind, aux, make_fn in cells:
                exe = self.get(ctx, kind, aux, int(bucket), make_fn,
                               dispatcher, warm=True)
                args = ((np.int32(bucket),)
                        if ctx.instrumented and kind == "read" else ())
                # fresh placement per cell: a donating executable
                # invalidates its input buffer, so cells must not share
                # one placed dummy
                dummy = dispatcher.place(host_dummy[int(bucket)])
                jax.block_until_ready(exe(dummy, *args, *ctx.bind))
                n += 1
        return n


class AsyncExecutor:
    """Slot-ring continuous batching over one service's dispatch path.

    Two daemon threads once `start()`ed:

      dispatch    waits on the micro-batcher, takes batches in admission
                  order, walks the service's work items (re-pinning per
                  run for the mutable service), resolves executables
                  through the cache, and LAUNCHES device work without
                  blocking; host work (inserts) is applied inline so a
                  later read run observes it — then rides the ring as an
                  already-final slot to keep completion in order.
      completion  pops slots in FIFO order, blocks on device results,
                  slices per request, resolves futures, records the
                  decomposed latencies.

    Stopped, it degrades to an inline engine: `drain()` launches and
    completes everything on the caller's thread, so synchronous tests
    and the `lookup()` convenience keep working without threads.
    """

    def __init__(self, service, slots: int = 4):
        if slots < 2:
            raise ValueError("async executor needs >= 2 slots "
                             "(double buffering)")
        self.svc = service
        self.slots = int(slots)
        self._ring: "queue.Queue" = queue.Queue(maxsize=self.slots)
        self._launch_mu = threading.Lock()   # serializes take+launch order
        self._inflight = 0
        self._inflight_cv = threading.Condition()
        self._batch_seq = 0                  # taken batches, under _launch_mu
        self._stop = threading.Event()
        self._dispatch_t: Optional[threading.Thread] = None
        self._complete_t: Optional[threading.Thread] = None

    @property
    def running(self) -> bool:
        return self._dispatch_t is not None

    # -- lifecycle -------------------------------------------------------
    def start(self) -> threading.Thread:
        """Spawn the dispatch + completion pair; returns the dispatch
        thread (the service exposes it as its flusher `_thread`)."""
        if self._dispatch_t is not None:
            return self._dispatch_t
        self._stop.clear()
        self._complete_t = threading.Thread(
            target=self._completion_loop, name="lookup-completer",
            daemon=True)
        self._dispatch_t = threading.Thread(
            target=self._dispatch_loop, name="lookup-dispatcher",
            daemon=True)
        self._complete_t.start()
        self._dispatch_t.start()
        return self._dispatch_t

    def stop(self) -> None:
        """Join both threads, completing every admitted request: the
        dispatch loop force-drains admissions on its way out, the
        completion loop runs the ring dry before honoring the sentinel,
        and a final inline drain covers the join window."""
        if self._dispatch_t is None:
            return
        self._stop.set()
        self.svc.batcher.wake()
        self._dispatch_t.join()
        self._ring.put(_STOP)
        self._complete_t.join()
        self._dispatch_t = None
        self._complete_t = None
        self._stop.clear()
        self.drain()   # anything admitted during the join window

    # -- loops -----------------------------------------------------------
    def _dispatch_loop(self) -> None:
        svc = self.svc
        while not self._stop.is_set():
            if svc.batcher.wait_ready(timeout=5.0,
                                      until=self._stop.is_set):
                with self._launch_mu:
                    batch = svc.batcher.take(force=False)
                    if batch:
                        self._launch_batch(batch)
        # exit path: launch everything admitted before stop()
        self._drain_launches()

    def _completion_loop(self) -> None:
        while True:
            slot = self._ring.get()
            if slot is _STOP:
                return
            self._complete_slot(slot)

    # -- launching -------------------------------------------------------
    def _launch_batch(self, batch) -> None:
        """Walk the service's work items lazily and in order: an insert
        item is APPLIED when reached, so the next run's pinned context
        observes it (the admission-order invariant), while device items
        launch without blocking.  The batch gets the next sequence
        number, the ``batch`` arg of every span its work records."""
        self._batch_seq += 1
        seq = self._batch_seq
        for item in self.svc._async_work_items(batch, seq):
            self._launch_item(item, seq)

    def _launch_item(self, item: WorkItem, seq: int) -> None:
        svc = self.svc
        group = item.group
        t_oldest = group[0].t_submit
        if item.kind == "insert":
            t0 = time.perf_counter()
            try:
                host = item.apply_fn(group)
            except BaseException as e:   # noqa: BLE001 — fail the run only
                self._put(_Slot(group=group, kind=item.kind, error=e,
                                t_submit_oldest=t_oldest, t_launch=t0,
                                is_insert=True, batch=seq))
                return
            self._put(_Slot(group=group, kind=item.kind, host=host,
                            m=sum(r.keys.size for r in group),
                            t_submit_oldest=t_oldest, t_launch=t0,
                            is_insert=True, batch=seq))
            return

        rec = svc.recorder
        with maybe_span(rec, "gather", batch=seq, n_requests=len(group)):
            keys = (group[0].keys if len(group) == 1
                    else np.concatenate([r.keys for r in group]))
        t0 = time.perf_counter()
        routed = isinstance(item.ctx, RoutedContext)
        instr = False
        try:
            # one span per launched slot, carrying the (contiguous,
            # admission-ordered) rid range it holds — the link between
            # request spans and the device work that served them
            with maybe_span(rec, "launch", batch=seq, kind=item.kind,
                            n_keys=int(keys.size), n_requests=len(group),
                            rid_first=group[0].rid,
                            rid_last=group[-1].rid) as span_args:
                ctx = item.ctx
                if routed:
                    routes = svc.dispatcher.routes_for(group, ctx.topology)
                    with maybe_span(rec, "enqueue", batch=seq):
                        out = svc.dispatcher.launch(
                            ctx, item.kind, item.aux, keys, routes=routes,
                            exec_cache=svc.exec_cache)   # never blocks
                    padded = out.padded
                else:
                    make_fn = ((lambda: ctx.read_fn) if item.kind == "read"
                               else (lambda: ctx.scan_fn(item.aux)))
                    with maybe_span(rec, "pad_place", batch=seq):
                        q, padded = svc.dispatcher.pad_and_place(keys)
                    with maybe_span(rec, "enqueue", batch=seq,
                                    padded=int(padded)):
                        exe = svc.exec_cache.get(ctx, item.kind, item.aux,
                                                 padded, make_fn,
                                                 svc.dispatcher)
                        instr = ctx.instrumented and item.kind == "read"
                        args = (np.int32(keys.size),) if instr else ()
                        out = exe(q, *args, *ctx.bind)   # async: no block
                if span_args is not None:
                    span_args["padded"] = int(padded)
        except BaseException as e:       # noqa: BLE001 — fail the group only
            self._put(_Slot(group=group, kind=item.kind, error=e,
                            t_submit_oldest=t_oldest, t_launch=t0,
                            batch=seq))
            return
        self._put(_Slot(group=group, kind=item.kind, out=out, m=keys.size,
                        padded=padded, t_submit_oldest=t_oldest,
                        t_launch=t0,
                        version=ctx.version if routed else ctx.key[0],
                        instrumented=instr, routed=routed, batch=seq))

    def _put(self, slot: _Slot) -> None:
        with self._inflight_cv:
            self._inflight += 1
            depth = self._inflight
        if self.svc.metrics is not None:
            self.svc.metrics.note_slot_depth(depth)
        if self.running:
            try:
                self._ring.put_nowait(slot)
            except queue.Full:     # the ring is full: wait, bounded
                with maybe_span(self.svc.recorder, "ring_wait",
                                batch=slot.batch):
                    self._ring.put(slot)
            return
        # inline mode has no completion thread to make room — keep the
        # bounded-ring invariant by completing the oldest slot here
        while True:
            try:
                self._ring.put_nowait(slot)
                return
            except queue.Full:
                self._complete_slot(self._ring.get())

    # -- completion ------------------------------------------------------
    def _complete_slot(self, slot: _Slot) -> None:
        svc = self.svc
        try:
            if slot.error is not None:
                for r in slot.group:
                    r.future._set_exception(slot.error)
            elif slot.is_insert:
                svc._complete_insert_slot(slot)
            else:
                rec = svc.recorder
                route_stats = None
                try:
                    with maybe_span(rec, "finalize", batch=slot.batch,
                                    kind=slot.kind, n_keys=slot.m,
                                    rid_first=slot.group[0].rid,
                                    rid_last=slot.group[-1].rid):
                        if slot.routed:
                            out, route_stats, _ = slot.out.finalize()
                        else:
                            out = self._to_host(slot, rec)
                except BaseException as e:   # noqa: BLE001 — device failure
                    for r in slot.group:     # fails the slot, not the loop
                        r.future._set_exception(e)
                    return
                t_end = time.perf_counter()
                with maybe_span(rec, "resolve", batch=slot.batch,
                                n_requests=len(slot.group)):
                    self._resolve(slot, out, route_stats, t_end)
        finally:
            with self._inflight_cv:
                self._inflight -= 1
                self._inflight_cv.notify_all()

    def _to_host(self, slot: _Slot, rec):
        """Block on a launched slot's device results and bring them to
        the host (`ShardedDispatcher.finalize`); an instrumented slot's
        packed stats vector crosses in one transfer of its own.  With a
        recorder the wait is split into ``device_wait``, ``copy_back``
        and ``stats_copy`` spans."""
        import jax

        payload, stats = slot.out if slot.instrumented else (slot.out, None)
        if rec is not None:
            with rec.span("device_wait", batch=slot.batch):
                jax.block_until_ready(slot.out)
        with maybe_span(rec, "copy_back", batch=slot.batch):
            out = self.svc.dispatcher.finalize(payload, slot.m)
        if stats is None:
            return out
        with maybe_span(rec, "stats_copy", batch=slot.batch):
            return out, np.asarray(stats)

    def _resolve(self, slot: _Slot, out, route_stats, t_end: float) -> None:
        """Everything after a slot's device results reached the host:
        health stats, per-request slicing, futures, request spans and
        the batch metrics."""
        svc = self.svc
        if slot.routed:
            # per-shard stats land in each SHARD generation's health
            # record; route skew feeds the metrics
            for ver, stats in route_stats:
                svc._note_health(ver, stats, t_end)
            if svc.metrics is not None:
                svc.metrics.observe_route(slot.out.counts, slot.out.padded)
        elif slot.instrumented:
            # instrumented read: route the device-reduced stats to the
            # record of the generation the slot ran on
            out, stats = out
            svc._note_health(slot.version, stats, t_end)
        off = 0
        for r in slot.group:
            end = off + r.keys.size
            r.future._set_result(
                tuple(o[off:end] for o in out)
                if isinstance(out, tuple) else out[off:end])
            off = end
        rec = svc.recorder
        if rec is not None:
            for r in slot.group:
                rec.request(r.rid, kind=r.kind, n_keys=r.keys.size,
                            t_submit=r.t_submit, t_launch=slot.t_launch,
                            t_end=t_end)
        svc.metrics.observe_batch(
            n_keys=slot.m, padded=slot.padded,
            n_requests=len(slot.group),
            t_oldest_submit=slot.t_submit_oldest,
            t_start=slot.t_launch, t_end=t_end,
            per_request=[(r.t_submit, r.keys.size, r.priority)
                         for r in slot.group])

    # -- synchronous faces ------------------------------------------------
    def _drain_launches(self) -> int:
        """Force-take and launch until the admission queue is empty."""
        n = 0
        with self._launch_mu:
            while True:
                batch = self.svc.batcher.take(force=True)
                if not batch:
                    return n
                self._launch_batch(batch)
                n += 1

    def _complete_ring_inline(self) -> None:
        """Run the completion side on the caller's thread (no-thread
        mode: synchronous tests, `lookup()` without `start()`)."""
        while True:
            try:
                slot = self._ring.get_nowait()
            except queue.Empty:
                return
            self._complete_slot(slot)

    def _wait_idle(self, timeout: Optional[float] = None) -> bool:
        with self._inflight_cv:
            return self._inflight_cv.wait_for(
                lambda: self._inflight == 0, timeout=timeout)

    def flush(self) -> bool:
        """Launch one due batch if any; wait until in-flight work is
        complete (same observable effect as the sync `flush`)."""
        launched = False
        with self._launch_mu:
            batch = self.svc.batcher.take(force=False)
            if batch:
                self._launch_batch(batch)
                launched = True
        self._settle()
        return launched

    def drain(self) -> int:
        """Force-dispatch until the queue is empty AND every launched
        slot has completed; returns the batch count.  Safe to call from
        any thread, with or without the loops running."""
        n = self._drain_launches()
        self._settle()
        return n

    def _settle(self) -> None:
        if self.running:
            self._wait_idle()
        else:
            self._complete_ring_inline()
