"""`LookupService`: admission -> micro-batch -> sharded dispatch (§9).

The serving analogue of `ServeEngine`, for index lookups instead of
tokens: clients `submit()` small uint64 key arrays and get futures;
a single flusher (either the background thread started by `start()`,
or explicit `flush()`/`drain()` calls in synchronous tests/benchmarks)
drains the micro-batcher in admission order and runs one sharded fused
lookup per batch.  One flusher + in-order draining gives FIFO completion
per client for free.

Results are LB positions (`D[pos]` is the smallest key >= query — the
paper's lower-bound semantics, DESIGN.md §2), bit-identical to a direct
single-device `repro.core` lookup on the same queries.

Hot-swap: `swap_keys(new_keys)` rebuilds off-thread-safe (outside every
lock) and publishes atomically; batches in flight complete against the
generation they were dispatched with — nothing drains, nothing blocks.

Executors (DESIGN.md §13): ``executor="sync"`` is the loop above — the
bit-exact reference every other path is pinned against.
``executor="async"`` swaps in the continuous-batching engine
(`serve.lookup.executor`): a pre-compiled executable cache keyed by
(generation, kind, batch bucket), a dispatch thread that launches device
work without blocking on it, and a bounded ring of in-flight slots
completed in FIFO order — admission and completion overlap the in-flight
device step, and steady-state p99 is bounded by kernel time instead of
Python dispatch + first-touch compiles.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from repro.core import spec as spec_mod
from repro.core.plan import BoundProgram
from repro.dist import sharding as SH
from repro.obs.alerts import AlertEngine, AlertRule, default_rules
from repro.obs.health import HealthMonitor
from repro.obs.trace import SpanRecorder, maybe_span
from repro.serve.common import MonotonicCounter
from repro.serve.lookup.admission import LookupFuture, MicroBatcher
from repro.serve.lookup.dispatch import (PAD_QUANTUM, RoutedContext,
                                         RoutedDispatcher, ShardedDispatcher)
from repro.serve.lookup.executor import (AsyncContext, AsyncExecutor,
                                         ExecutableCache, WorkItem)
from repro.serve.lookup.metrics import ServiceMetrics
from repro.serve.lookup.registry import (DEFAULT_NAME, Generation,
                                         IndexRegistry, RoutedGeneration)
from repro.serve.lookup.topology import ShardTopology


#: One source of truth for the serving-default hyperparameters — the
#: numbers the README/DESIGN-cited throughput sweep publishes; the serve
#: driver demos the same configuration.
DEFAULT_HYPER = {
    "rmi": dict(branching=4096),
    "pgm": dict(eps=64),
    "radix_spline": dict(eps=32, radix_bits=16),
}


def default_spec(index: str, backend: str = "jnp") -> spec_mod.IndexSpec:
    """The serving-default `IndexSpec` for one index family."""
    return spec_mod.IndexSpec(index, dict(DEFAULT_HYPER.get(index, {})),
                              backend=backend).validated()


@dataclasses.dataclass(frozen=True)
class LookupServiceConfig:
    index: str = "rmi"                 # repro.core.base.REGISTRY name
    hyper: Dict[str, Any] = dataclasses.field(default_factory=dict)
    last_mile: Optional[str] = None    # None -> the build's own choice
    backend: str = "jnp"               # LookupPlan backend ("jnp" | "pallas")
    max_batch: int = 4096              # keys per dispatch (flush trigger)
    deadline_ms: float = 2.0           # oldest-request flush deadline
    #: Per-latency-class flush budgets in ms (DESIGN.md §17 satellite),
    #: e.g. ``{"interactive": 1.0, "batch": 20.0}``: the deadline
    #: trigger fires at the earliest pending (submit + class budget);
    #: unknown classes fall back to ``deadline_ms``.  None = single
    #: deadline for everything (classic behavior).
    class_deadline_ms: Optional[Dict[str, float]] = None
    pad_quantum: int = PAD_QUANTUM
    max_client_keys: Optional[int] = None   # per-client pending-key cap
    client_rate: Optional[tuple] = None     # per-client (rate keys/s, burst)
    max_scan_length: int = 4096             # per-request scan-window cap
    #: Declarative alternative to index/hyper/backend/last_mile: when
    #: set, the spec wins WHOLESALE (the four field-wise knobs are
    #: ignored) — one serializable value addresses the whole build.
    spec: Optional[spec_mod.IndexSpec] = None
    #: Dispatch engine: "sync" (serial take -> block -> complete, the
    #: bit-exact reference) or "async" (continuous batching — executable
    #: cache + double buffering + slot ring, DESIGN.md §13).
    executor: str = "sync"
    slots: int = 4                          # async in-flight slot ring depth
    #: Batch buckets the async warm-up pre-compiles; () = every pow2
    #: bucket from pad_quantum up to padded(max_batch) — the shapes
    #: steady traffic actually dispatches.
    warm_buckets: Tuple[int, ...] = ()
    #: Scan lengths warmed alongside (each is a compile-shape axis).
    warm_scan_lengths: Tuple[int, ...] = ()
    #: Observability (DESIGN.md §14).  ``trace`` turns on the structured
    #: span recorder (bounded ring of ``trace_capacity`` spans: per-
    #: request ids from admission through launch/completion, plus
    #: compile/hot-swap/warm-up/compaction lifecycle spans) exported as
    #: Chrome-trace JSON via ``service.recorder.to_chrome()``.  Off by
    #: default: the disabled path costs one ``is None`` check per site.
    trace: bool = False
    trace_capacity: int = 65536
    #: Rolling-window metrics resolution: the ring holds ``window_slots``
    #: sub-histograms of ``window_slot_s`` seconds each, merged at read
    #: by ``metrics.windowed(window_s=...)``.
    window_slot_s: float = 0.5
    window_slots: int = 240
    #: Optional p99 SLO target: request latencies above it burn error
    #: budget, reported per window (`slo_budget_burn`).
    slo_p99_ms: Optional[float] = None
    #: Index-health telemetry (DESIGN.md §15).  On by default: reads
    #: dispatch the plan's instrumented executable — bit-identical
    #: positions plus O(buckets) device-reduced stats per batch — and a
    #: `HealthMonitor` keeps per-generation displacement/traffic/drift
    #: records behind `health_snapshot()` / `/health.json`.
    health: bool = True
    #: Alert rules evaluated over `health_snapshot()` keys; None -> the
    #: shipped `repro.obs.alerts.default_rules()`, () -> no rules.
    alert_rules: Optional[Tuple[AlertRule, ...]] = None
    #: Range-routed serving topology (DESIGN.md §16).  ``shards > 1``
    #: partitions the key space into that many equal-count ranges, each
    #: with its own (smaller) index generation, and replaces broadcast
    #: dispatch with scatter/gather routing — per-device work drops from
    #: O(batch) to O(batch/shards).  ``topology`` pins an explicit
    #: `ShardTopology` instead (wins over ``shards``/``replicas``, and
    #: forces the routed path even with one shard).
    shards: int = 1
    replicas: int = 1                       # read fan-out per shard
    topology: Optional[ShardTopology] = None
    #: Per-shard spec search: each shard's `IndexSpec` tuned against
    #: ONLY its slice (per-shard byte budget = Tuner.max_bytes / shards).
    #: None -> every shard reuses the service's resolved spec.
    shard_tuner: Optional[spec_mod.Tuner] = None
    #: Donate the staged query buffer to XLA (the executable reuses its
    #: memory).  None -> auto: on for non-CPU backends, off on CPU where
    #: donation is a no-op with a warning.
    donate_queries: Optional[bool] = None
    #: Self-driving tuning (DESIGN.md §17): an
    #: `repro.autotune.AutotuneConfig` attaches a `ShadowRetuner` to
    #: this service — alert-triggered workload-aware retunes, oracle-
    #: verified hot-swaps, `/autotune.json` surface.  With
    #: ``autotune.daemon`` the retuner thread starts/stops with the
    #: service; otherwise drive it via ``service.autotune.poll_once()``.
    autotune: Optional[Any] = None

    def resolved_spec(self) -> spec_mod.IndexSpec:
        """The validated `IndexSpec` every build of this service uses."""
        if self.spec is not None:
            return self.spec.validated()
        return spec_mod.coerce(self.index, self.hyper,
                               backend=self.backend,
                               last_mile=self.last_mile)


class LookupService:
    def __init__(self, keys: np.ndarray,
                 config: Optional[LookupServiceConfig] = None,
                 mesh=None, counter: Optional[MonotonicCounter] = None):
        self.cfg = config if config is not None else LookupServiceConfig()
        if self.cfg.executor not in ("sync", "async"):
            raise ValueError(
                f"executor must be 'sync' or 'async', "
                f"got {self.cfg.executor!r}")
        #: §14 span recorder, or None when tracing is off — every
        #: instrumentation site on the serve path shares this one object
        self.recorder = (SpanRecorder(self.cfg.trace_capacity)
                         if self.cfg.trace else None)
        self.registry = IndexRegistry()
        self.registry.recorder = self.recorder
        #: §15 per-generation health monitor, or None when disabled —
        #: attached to the registry BEFORE the first publish so the
        #: initial generation gets a record too
        shards_hint = (self.cfg.topology.n_shards
                       if self.cfg.topology is not None
                       else max(1, self.cfg.shards))
        self.health = (HealthMonitor(slot_s=self.cfg.window_slot_s,
                                     n_slots=self.cfg.window_slots,
                                     keep=max(8, 2 * (shards_hint + 1)))
                       if self.cfg.health else None)
        self.registry.health = self.health
        #: §15 alert engine — always present (rules may be empty); it
        #: only evaluates when asked (`check_alerts`/endpoints/doctor)
        self.alerts = AlertEngine(
            rules=(default_rules() if self.cfg.alert_rules is None
                   else self.cfg.alert_rules))
        self.dispatcher = ShardedDispatcher(
            mesh=mesh, pad_quantum=self.cfg.pad_quantum,
            recorder=self.recorder)
        #: the broadcast dispatcher's mesh bounds every placement: key
        #: arrays go onto these devices only (a routed topology splits
        #: them into lanes), so a one-device mesh keeps the service on
        #: one chip of a larger host
        self._broadcast = self.dispatcher
        self._devices = list(self.dispatcher.mesh.devices.flat)
        self.registry.placement = self._place_keys
        #: hot-swap re-warms that failed (`_warm_retry`); a failed warm
        #: leaves first-touch compiles on the serving path
        self.warm_failures = 0
        self.last_warm_error: Optional[BaseException] = None
        self.metrics = ServiceMetrics(
            slo_p99_ms=self.cfg.slo_p99_ms,
            window_slot_s=self.cfg.window_slot_s,
            window_slots=self.cfg.window_slots)
        self.batcher = MicroBatcher(
            self.cfg.max_batch, self.cfg.deadline_ms / 1e3,
            counter=counter if counter is not None else MonotonicCounter(),
            max_client_keys=self.cfg.max_client_keys,
            client_rate=self.cfg.client_rate,
            recorder=self.recorder,
            class_deadlines=(
                {k: v / 1e3
                 for k, v in self.cfg.class_deadline_ms.items()}
                if self.cfg.class_deadline_ms is not None else None))
        self._dispatch_lock = threading.Lock()   # one batch at a time
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._warm_thread: Optional[threading.Thread] = None
        self.exec_cache = ExecutableCache(metrics=self.metrics,
                                          recorder=self.recorder)
        self._async = (AsyncExecutor(self, slots=self.cfg.slots)
                       if self.cfg.executor == "async" else None)
        # routed state: the current RoutedGeneration (None on the
        # broadcast path) and the pinned-context cache keyed on
        # (generation version, lane epoch, instrumented)
        self._routed: Optional[RoutedGeneration] = None
        self._rctx_cache: Dict[Tuple, RoutedContext] = {}
        import jax
        self._donate = (self.cfg.donate_queries
                        if self.cfg.donate_queries is not None
                        else jax.default_backend() != "cpu")
        # every publish lands here: routed topology/router updates for
        # both executors, plus (async only) invalidation-on-swap — so
        # compaction rebuilds (which publish without going through
        # swap_keys) evict stale executables too
        self.registry.subscribe(self._on_publish)
        self.swap_keys(keys)
        #: §17 shadow retuner, or None — constructed AFTER the first
        #: publish so its trigger polls always see a live generation
        if self.cfg.autotune is not None:
            from repro.autotune import ShadowRetuner
            self.autotune = ShadowRetuner(self, self.cfg.autotune)
        else:
            self.autotune = None

    # -- index lifecycle -------------------------------------------------
    def _resolve_topology(self, keys) -> Optional[ShardTopology]:
        """The serving topology for one key set, or None for broadcast.
        An explicit ``cfg.topology`` always routes (even single-shard —
        that is the degeneration-parity path); ``shards > 1`` builds an
        equal-count partition fresh per key set."""
        if self.cfg.topology is not None:
            return self.cfg.topology
        if self.cfg.shards > 1:
            return ShardTopology.from_keys(keys, self.cfg.shards,
                                           self.cfg.replicas)
        return None

    def _place_keys(self, keys: np.ndarray, shard: Optional[int],
                    topology: Optional[ShardTopology]):
        """Registry placement hook: a broadcast generation's keys go onto
        every device of the mesh; routed shard ``s``'s keys onto the
        device of its first lane (`RoutedDispatcher` assigns lanes with
        the same `shard_replica_groups` walk; other replicas copy from
        there when their lane context is built)."""
        import jax

        if shard is None:
            return jax.device_put(keys, self._broadcast.replicated)
        groups = SH.shard_replica_groups(self._devices, topology.replicas)
        return jax.device_put(keys, groups[shard][0])

    def swap_keys(self, keys: np.ndarray) -> Generation:
        """Rebuild on a fresh key set and hot-swap it in (no draining).
        Builds go through the config's resolved `IndexSpec`, so the
        published generation is spec-addressable (`Generation.spec`).
        With a routed topology this publishes one generation per range
        plus the topology, as a single atomic `RoutedGeneration`."""
        keys = np.asarray(keys, dtype=np.uint64)
        topo = self._resolve_topology(keys)
        if topo is None:
            return self.registry.build_and_publish(
                self.cfg.resolved_spec(), keys)
        return self.registry.build_and_publish_routed(
            self.cfg.resolved_spec(), keys, topo,
            tuner=self.cfg.shard_tuner)

    @property
    def generation(self) -> Generation:
        return self.registry.current()

    # -- client surface --------------------------------------------------
    def submit(self, keys, client=None,
               priority: str = "interactive") -> LookupFuture:
        """Admit one request; never blocks.  Completion needs a flusher:
        either the background thread (`start()`/`with svc:`) or explicit
        `flush()`/`drain()` calls — a future submitted with neither
        stays pending until one of them runs.  ``client`` is an optional
        fairness id: with `max_client_keys` configured, an over-backlog
        client's submit raises `ClientBacklogFull` instead of queueing.
        ``priority`` is the latency class: it selects the flush budget
        (``cfg.class_deadline_ms``) and the per-class latency row in
        `ServiceMetrics`."""
        _, fut = self.batcher.submit(keys, client=client,
                                     priority=priority)
        return fut

    def scan(self, keys, length: int, client=None) -> LookupFuture:
        """Admit one range-scan request (op kind "scan"): the future
        resolves to ``(positions, window)`` where ``window[i]`` holds the
        ``length`` records from ``LB(keys[i])`` (UINT64_MAX sentinel past
        the end) — the plan's `compile_scan` materialization, so YCSB-E
        traces execute end-to-end instead of position-only."""
        # bound the client-supplied length: the window is a [B, length]
        # gather AND a compile-shape axis (each distinct length caches a
        # compiled executable), so it must not be client-unbounded.  A
        # routed topology tightens the bound to the smallest shard — a
        # shard's spill window only repairs up to min_shard_len records.
        gen = self.generation
        max_len = self.cfg.max_scan_length
        if isinstance(gen, RoutedGeneration):
            max_len = min(max_len, gen.max_scan_len)
        if not 1 <= length <= max_len:
            raise ValueError(f"scan length must be in [1, {max_len}]")
        # reject point-only indexes at admission (cheapest point); the
        # per-group guard in _complete_run still covers the race where a
        # hot-swap to a point-only index lands after admission
        point_only = (gen.point_only if isinstance(gen, RoutedGeneration)
                      else gen.plan.point_only)
        if point_only:
            raise ValueError(
                f"index {gen.plan.name!r} is point-only: no scans")
        _, fut = self.batcher.submit(keys, kind="scan", aux=int(length),
                                     client=client)
        return fut

    def lookup(self, keys, timeout: Optional[float] = 30.0) -> np.ndarray:
        """Synchronous convenience: submit + ensure progress + wait."""
        fut = self.submit(keys)
        if self._thread is None:
            self.drain()
        return fut.result(timeout)

    # -- flushing --------------------------------------------------------
    def _dispatch_once(self, force: bool = False) -> bool:
        """Take + process one batch; returns whether one was taken.

        Serialized by `_dispatch_lock`: take order == dispatch order ==
        completion order, which is the FIFO guarantee.
        """
        with self._dispatch_lock:
            batch = self.batcher.take(force=force)
            if not batch:
                return False
            self._process_batch(batch)
            return True

    @staticmethod
    def _runs(batch, key):
        """Yield maximal consecutive runs of `batch` sharing `key(req)`,
        in order — the one splitter every dispatch path shares."""
        i = 0
        while i < len(batch):
            j = i
            while j < len(batch) and key(batch[j]) == key(batch[i]):
                j += 1
            yield batch[i:j]
            i = j

    def _process_batch(self, batch) -> None:
        """Split the taken batch into consecutive same-kind runs and
        dispatch each — admission order is preserved within and across
        runs, so FIFO completion per client still holds.  The lookup
        context (`_pin_context`) is read ONCE for the whole batch: a
        hot-swap lands between batches, never inside one.  (The mutable
        subclass re-pins per run instead — an insert run changes the
        delta and a later read run in the same batch must observe it.)"""
        ctx = self._pin_context()
        for run in self._runs(batch, key=lambda r: r.kind):
            self._dispatch_run(run[0].kind, run, ctx)

    def _dispatch_run(self, kind: str, run, ctx=None) -> None:
        """Route one same-kind run; subclasses add kinds (inserts)."""
        if ctx is None:
            ctx = self._pin_context()
        if isinstance(ctx, RoutedContext):
            if kind == "scan":
                for group in self._runs(run, key=lambda r: r.aux):
                    self._complete_routed("scan", list(group),
                                          int(group[0].aux), ctx)
            else:
                self._complete_routed("read", list(run), 0, ctx)
            return
        lookup_fn, scan_for, version = ctx
        if kind == "scan":
            self._dispatch_scans(run, scan_for)
        else:
            self._dispatch_reads(run, lookup_fn, version)

    def _pin_context(self):
        """``(lookup_fn, m -> scan executable, version)`` bound to ONE
        immutable generation — the snapshot a batch completes against.
        With health on, ``lookup_fn`` is the plan's INSTRUMENTED
        executable (same positions bit-for-bit, plus device-reduced
        stats); ``version`` routes those stats to the right record.
        Routed generations pin a `RoutedContext` instead (the whole
        topology + per-lane executables snapshot)."""
        gen = self.registry.current()
        if isinstance(gen, RoutedGeneration):
            return self._routed_context(gen)
        read, scan_for, ops = self._programs(gen)
        return (BoundProgram(read, ops),
                lambda m: BoundProgram(scan_for(m), ops), gen.version)

    def _programs(self, gen: Generation, dispatcher=None, donate=False):
        """``(read program, m -> scan program, operands)`` of one
        generation on one dispatcher: the read program is the
        instrumented one when health is on; the operands are the
        generation's key array and index state placed the way the
        dispatcher runs batches."""
        d = self.dispatcher if dispatcher is None else dispatcher
        read = gen.program("instr" if self.health is not None else "lb",
                           mesh=d.mesh, donate=donate)
        return (read, lambda m: gen.program("scan", m=int(m), mesh=d.mesh),
                gen.operands_on(d))

    def _routed_context(self, gen: RoutedGeneration) -> RoutedContext:
        """One executable-cache-addressable context per (generation,
        lane layout): every (shard, replica) lane gets its own
        `AsyncContext` keyed ``(shard version, replica)`` so AOT
        executables stay committed to their lane's device."""
        instrumented = self.health is not None
        key = (gen.version, self.dispatcher.lanes_epoch, instrumented)
        rctx = self._rctx_cache.get(key)
        if rctx is not None:
            return rctx
        spill_len = min(self.cfg.max_scan_length, gen.max_scan_len)
        lane_ctxs = []
        for s, sgen in enumerate(gen.shards):
            reps = []
            spill = gen.spill(s, spill_len)
            sample_key = int(np.asarray(sgen.data[:1])[0])
            for r, lane in enumerate(self.dispatcher.lanes[s]):
                read_fn, scan_fn, ops = self._programs(sgen, lane,
                                                       donate=self._donate)
                if spill is not None:
                    ops = {**ops, "spill": lane.place_operands(spill)}
                reps.append(AsyncContext(
                    key=(sgen.version, r),
                    read_fn=read_fn,
                    scan_fn=scan_fn,
                    bind=(ops,),
                    sample_key=sample_key,
                    instrumented=instrumented))
            lane_ctxs.append(tuple(reps))
        rctx = RoutedContext(
            topology=gen.topology,
            lane_ctxs=tuple(lane_ctxs),
            offsets=tuple(gen.topology.offsets),
            versions=gen.shard_versions,
            version=gen.version,
            instrumented=instrumented)
        self._rctx_cache[key] = rctx
        return rctx

    def _complete_routed(self, kind: str, group, aux: int,
                         rctx: RoutedContext) -> None:
        """Synchronous routed dispatch of one same-(kind, aux) group:
        scatter over shard lanes, finalize (gather in admission order),
        complete futures — the routed twin of `_complete_run`."""
        keys = (group[0].keys if len(group) == 1
                else np.concatenate([r.keys for r in group]))
        t0 = time.perf_counter()
        try:
            routes = self.dispatcher.routes_for(group, rctx.topology)
            handle = self.dispatcher.launch(rctx, kind, aux, keys,
                                            routes=routes)
            out, stats, padded = handle.finalize()
        except BaseException as e:  # noqa: BLE001 — fail the group only
            for r in group:
                r.future._set_exception(e)
            return
        t1 = time.perf_counter()
        for ver, st in stats:
            self._note_health(ver, st, t1)
        self.metrics.observe_route(handle.counts, padded)
        self._finish_group(group, out, t0, t1, keys.size, padded)

    def _complete_run(self, group, make_fn, version: int = -1,
                      instrumented: bool = False) -> None:
        """Dispatch one request group through ``make_fn()`` and complete
        its futures in order; tuple results (scans) are sliced per array.
        Failures fail the group's futures, never the flusher — including
        executable CONSTRUCTION failures (``make_fn`` runs inside the
        guard: scan compilation rejects point-only plans).  Instrumented
        reads strip the stats dict off the result and fold it into the
        health record of ``version`` — futures never see it."""
        keys = (group[0].keys if len(group) == 1
                else np.concatenate([r.keys for r in group]))
        t0 = time.perf_counter()
        try:
            out = self.dispatcher(make_fn(), keys,
                                  n_valid_arg=instrumented)
        except BaseException as e:  # noqa: BLE001 — fail the group, not the flusher
            for r in group:
                r.future._set_exception(e)
            return
        t1 = time.perf_counter()
        if instrumented:
            out, stats = out
            self._note_health(version, stats, t1)
        self._finish_group(group, out, t0, t1, keys.size,
                           self.dispatcher.padded_size(keys.size))

    def _finish_group(self, group, out, t0: float, t1: float,
                      n_keys: int, padded: int) -> None:
        """Shared completion tail of both sync paths: slice the batch
        result per request in admission order, resolve futures, record
        request spans, and fold the batch into the metrics."""
        off = 0
        for r in group:
            end = off + r.keys.size
            r.future._set_result(tuple(o[off:end] for o in out)
                                 if isinstance(out, tuple) else out[off:end])
            off = end
        if self.recorder is not None:
            for r in group:
                self.recorder.request(r.rid, kind=r.kind,
                                      n_keys=r.keys.size,
                                      t_submit=r.t_submit,
                                      t_launch=t0, t_end=t1)
        self.metrics.observe_batch(
            n_keys=n_keys,
            padded=padded,
            n_requests=len(group),
            t_oldest_submit=group[0].t_submit,
            t_start=t0, t_end=t1,
            per_request=[(r.t_submit, r.keys.size, r.priority)
                         for r in group])

    def _dispatch_reads(self, batch, lookup_fn, version: int = -1) -> None:
        self._complete_run(batch, lambda: lookup_fn, version=version,
                           instrumented=self.health is not None)

    def _dispatch_scans(self, batch, scan_for) -> None:
        """Dispatch a run of scan requests, grouped by scan length (the
        static window width is a compile-shape axis).  Futures resolve to
        ``(positions, window)`` per request.  `_dispatch_run` is the one
        resolver of the pinned context these run against."""
        for group in self._runs(batch, key=lambda r: r.aux):
            m = int(group[0].aux)
            self._complete_run(group, lambda m=m: scan_for(m))

    # -- async executor plumbing (DESIGN.md §13) --------------------------
    def _async_context(self) -> AsyncContext:
        """Pin one generation as an executable-cache-addressable context:
        the async analogue of `_pin_context` (same snapshot semantics —
        a hot-swap lands between batches, never inside one).  Routed
        generations return the (cached) `RoutedContext` — the executor
        branches on the type."""
        gen = self.registry.current()
        if isinstance(gen, RoutedGeneration):
            return self._routed_context(gen)
        read_fn, scan_fn, ops = self._programs(gen)
        return AsyncContext(
            key=(gen.version,),
            read_fn=read_fn,
            scan_fn=scan_fn,
            bind=(ops,),
            sample_key=int(np.asarray(gen.data[:1])[0]),
            instrumented=self.health is not None)

    def _async_work_items(self, batch):
        """Lazily yield `WorkItem`s for one taken batch, in admission
        order — the async twin of `_process_batch`, with the context
        pinned ONCE for the whole batch (the mutable subclass re-pins
        per run and interleaves insert application)."""
        ctx = self._async_context()
        for run in self._runs(batch, key=lambda r: r.kind):
            yield from self._async_items_for_run(run[0].kind, run, ctx)

    def _async_items_for_run(self, kind, run, ctx):
        if kind == "scan":
            # scan length is a compile-shape axis: split like the sync path
            for group in self._runs(run, key=lambda r: r.aux):
                yield WorkItem(kind="scan", group=list(group), ctx=ctx,
                               aux=int(group[0].aux))
        else:
            yield WorkItem(kind="read", group=list(run), ctx=ctx)

    def _complete_insert_slot(self, slot) -> None:
        """Resolve a host-ready insert slot (mutable service only)."""
        raise NotImplementedError(
            "insert completion on a read-only service")

    def _resolved_warm_buckets(self, dispatcher=None):
        d = self.dispatcher if dispatcher is None else dispatcher
        if self.cfg.warm_buckets:
            return tuple(sorted({d.padded_size(int(b))
                                 for b in self.cfg.warm_buckets}))
        # every pow2 bucket steady traffic can dispatch at: quantum ..
        # padded(max_batch) — log2-many executables, compiled once
        buckets, b = [], d.padded_size(1)
        top = d.padded_size(self.cfg.max_batch)
        while b < top:
            buckets.append(b)
            b = d.padded_size(b + 1)
        buckets.append(top)
        return tuple(buckets)

    def warm_now(self) -> int:
        """Synchronously prime the executable cache for the CURRENT
        generation over the configured warm buckets; returns the number
        of warmed cells.  `start()` runs this before serving; hot-swaps
        re-run it off-thread (`_on_publish`)."""
        if self._async is None:
            return 0
        ctx = self._async_context()
        if isinstance(ctx, RoutedContext):
            return self._warm_routed(ctx)
        buckets = self._resolved_warm_buckets()
        with maybe_span(self.recorder, "warmup", cat="lifecycle",
                        version=ctx.key[0], n_buckets=len(buckets)):
            return self.exec_cache.warmup(
                ctx, buckets, self.dispatcher,
                scan_lengths=self.cfg.warm_scan_lengths)

    def warm_wait(self, timeout: Optional[float] = None) -> None:
        """Block until the background re-warm kicked off by the last
        hot-swap publish finishes (no-op when none is in flight) — so a
        caller that just swapped can measure steady-state serving
        without racing the warm thread's compiles for CPU."""
        w = self._warm_thread
        if w is not None and w.is_alive():
            w.join(timeout)

    def _warm_routed(self, rctx: RoutedContext) -> int:
        """Prime every (shard, replica) lane's executables on that
        lane's own dispatcher — AOT executables are device-committed,
        so each lane needs its own warm pass."""
        n = 0
        with maybe_span(self.recorder, "warmup", cat="lifecycle",
                        version=rctx.version,
                        n_shards=self.dispatcher.n_shards):
            for s, grp in enumerate(self.dispatcher.lanes):
                for r, lane in enumerate(grp):
                    n += self.exec_cache.warmup(
                        rctx.lane_ctxs[s][r],
                        self._resolved_warm_buckets(lane), lane,
                        scan_lengths=self.cfg.warm_scan_lengths)
        return n

    def _on_publish(self, name: str, gen) -> None:
        """Registry publish hook: track the routed topology (both
        executors route at admission through it), then — async only —
        evict stale generations' executables and re-warm the new one
        WITHOUT blocking the publisher (a compaction thread may be
        mid-swap holding its own locks — warming there would deadlock)."""
        if name != DEFAULT_NAME:
            return
        if isinstance(gen, RoutedGeneration):
            if not isinstance(self.dispatcher, RoutedDispatcher):
                self.dispatcher = RoutedDispatcher(
                    gen.topology, devices=self._devices,
                    pad_quantum=self.cfg.pad_quantum,
                    recorder=self.recorder)
            else:
                self.dispatcher.set_replicas(gen.topology)
            self._routed = gen
            self._rctx_cache.clear()
            # admission-time routing: each submit tags its request with
            # (topology, shard ids); a later hot-swap invalidates the
            # tag by object identity and dispatch re-routes
            self.batcher.router = (
                lambda keys, t=gen.topology: (t, t.route(keys)))
            keep = (gen.version,) + gen.shard_versions
        else:
            self._routed = None
            self.batcher.router = None
            keep = gen.version
        if self._async is None:
            return
        self.exec_cache.invalidate(keep_version=keep)
        if self._thread is None:
            # not serving: start() warms synchronously before the first
            # dispatch, and a never-started service must not leave a
            # compile thread behind at interpreter teardown
            return
        t = threading.Thread(target=self._warm_retry,
                             name="lookup-warmer", daemon=True)
        self._warm_thread = t
        t.start()

    def rebalance_replicas(self, total_replicas: Optional[int] = None,
                           window_s: float = 10.0) -> Tuple[int, ...]:
        """Re-apportion replica seats to the shards that actually take
        the traffic (the PR 8 per-shard traffic windows): the hottest
        range gets the replicas.  Only the read fan-out changes — split
        points and offsets stay, so admission-time routes remain valid.
        Returns the new per-shard replica counts."""
        gen = self.registry.current()
        if not isinstance(gen, RoutedGeneration):
            raise ValueError("rebalance_replicas needs a routed topology")
        masses = []
        for sgen in gen.shards:
            mass = 0.0
            if self.health is not None:
                rec = self.health.get(sgen.version)
                if rec is not None:
                    mass = float(np.sum(rec.traffic_window(window_s)))
            masses.append(mass)
        topo = gen.topology.rebalanced_from_masses(
            masses, total_replicas=total_replicas)
        if self.dispatcher.set_replicas(topo):
            self._rctx_cache.clear()
        return topo.replicas

    def _warm_retry(self) -> None:
        """Warm the current context, tolerating construction windows
        (the mutable service publishes its first generation before its
        view pointer exists — retry briefly).  A warm that still fails is
        counted in ``warm_failures`` with its error kept in
        ``last_warm_error``: serving goes on with first-touch compiles,
        and a caller that must not (the chip smoke) checks the count."""
        deadline = time.perf_counter() + 5.0
        while True:
            try:
                self.warm_now()
                return
            except Exception as e:   # noqa: BLE001 — counted, not fatal
                if time.perf_counter() >= deadline:
                    self.warm_failures += 1
                    self.last_warm_error = e
                    return
                time.sleep(0.005)

    # -- index-health telemetry (DESIGN.md §15) ---------------------------
    def _note_health(self, version: int, stats, t_end: float) -> None:
        """Fold one completed batch's device-reduced stats into the
        health record of the generation it ran against (both executors'
        completion paths land here)."""
        if self.health is not None:
            self.health.accumulate(version, stats, t=t_end)

    def health_snapshot(self, window_s: float = 10.0) -> Dict[str, float]:
        """ONE flat key namespace over service + window + model health —
        what alert rules evaluate and `/health.json` exports: the
        lifetime `ServiceMetrics` snapshot, the trailing-window metrics
        under a ``window_`` prefix (``window_covered_s`` reports actual
        coverage), and the current generation's health keys."""
        snap = self.metrics.snapshot()
        win = self.metrics.windowed(window_s)
        snap["window_covered_s"] = win.pop("window_s")
        snap.update({f"window_{k}": v for k, v in win.items()})
        if self.health is not None:
            snap.update(self.health.snapshot(window_s))
        snap["trace_dropped"] = float(self.recorder.n_dropped
                                      if self.recorder is not None else 0)
        snap["inflight_saturation"] = (
            snap.get("mean_inflight_slots", 0.0) / self.cfg.slots
            if self._async is not None and self.cfg.slots else 0.0)
        snap["serving"] = 1.0 if self._thread is not None else 0.0
        if self.autotune is not None:
            st = self.autotune.status()
            snap["autotune_alive"] = 1.0 if st.get("alive") else 0.0
            snap["autotune_triggered"] = float(st.get("n_triggered", 0))
            snap["autotune_swapped"] = float(st.get("n_swapped", 0))
            snap["autotune_rejected"] = float(st.get("n_rejected", 0))
        return snap

    def check_alerts(self, window_s: float = 10.0) -> list:
        """Evaluate every alert rule against a fresh `health_snapshot`;
        returns the events emitted by THIS evaluation (state transitions
        only — steady firing/ok emits nothing)."""
        return self.alerts.evaluate(self.health_snapshot(window_s))

    def health_status(self, window_s: float = 10.0):
        """``(http_status, doc)`` for liveness surfaces (`/healthz`):
        503 when the background flusher is not running or a critical
        alert is firing, 200 otherwise.  Evaluates the rules first so
        the answer reflects NOW, not the last poll."""
        self.check_alerts(window_s)
        firing = self.alerts.firing()
        critical = self.alerts.firing(severity="critical")
        serving = self._thread is not None
        ok = serving and not critical
        doc = {"status": "ok" if ok else "unhealthy",
               "serving": serving,
               "firing": firing, "critical": critical}
        return (200 if ok else 503), doc

    def flush(self) -> bool:
        """Dispatch one due batch if any (size or deadline trigger)."""
        if self._async is not None:
            return self._async.flush()
        return self._dispatch_once(force=False)

    def drain(self) -> int:
        """Force-dispatch until the queue is empty; returns batch count.
        In async mode this also waits for every in-flight slot, so no
        future is left unresolved when it returns."""
        if self._async is not None:
            return self._async.drain()
        n = 0
        while self._dispatch_once(force=True):
            n += 1
        return n

    # -- background flusher ----------------------------------------------
    def start(self) -> "LookupService":
        if self._thread is not None:
            return self
        if self._async is not None:
            # prime the common buckets BEFORE serving: steady-state
            # dispatch then never traces or compiles (§13 warm-up)
            self.warm_now()
            self._thread = self._async.start()
            self._start_autotune()
            return self
        self._stop.clear()

        def _loop():
            while not self._stop.is_set():
                if self.batcher.wait_ready(timeout=5.0,
                                           until=self._stop.is_set):
                    self._dispatch_once(force=False)
            self.drain()   # complete everything admitted before stop()

        self._thread = threading.Thread(
            target=_loop, name="lookup-flusher", daemon=True)
        self._thread.start()
        self._start_autotune()
        return self

    def _start_autotune(self) -> None:
        """Start the shadow-retuner daemon alongside the flusher (only
        when the config asked for one — `poll_once` stays available for
        explicit/test-driven retunes either way)."""
        at = self.autotune
        if at is not None and at.cfg.daemon:
            at.start()

    def stop(self) -> None:
        """Stop the background flusher, completing everything admitted so
        far.  The service stays usable afterwards — in synchronous mode
        (submit + flush/drain), or via a later start()."""
        if self._thread is None:
            return
        if self.autotune is not None:
            self.autotune.stop()   # no retunes against a draining service
        if self._async is not None:
            self._async.stop()
            self._thread = None
            w = self._warm_thread
            if w is not None and w.is_alive():
                w.join()   # never strand a compile thread past stop()
            return
        self._stop.set()
        self.batcher.wake()
        self._thread.join()
        self._thread = None
        self.drain()       # anything admitted during the join window
        self._stop.clear()

    def __enter__(self) -> "LookupService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
