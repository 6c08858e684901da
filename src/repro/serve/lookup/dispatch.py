"""Sharded dispatch: one plan-compiled lookup over the `data` mesh axis (§9.2).

Generalizes mode (c) of `benchmarks/parallel_scaling.py` into a reusable
engine.  The query batch is padded to a power-of-two bucket (a multiple
of the shard count), placed over the mesh's data axis through the
`repro.dist.sharding` activation rule for the logical `batch` axis, and
run through a `repro.core.plan.LookupPlan` program — the dispatcher
shards PLANS, not hand-rolled closures: pass a plan and it runs the
plan's program for the requested backend with the plan's operands (key
array + index state) replicated over the mesh, or pass any callable
(e.g. a merged-view or scan executable with its operands bound)
directly.  The mesh's axes are `Auto`, so jit picks the partitioning of
the jnp lookup up from the input sharding; Pallas kernels run per
device under `jax.shard_map` (`LookupPlan.program(mesh=...)`).  The
very same program serves 1 or N devices; every device holds the key
array and the index state.

Bit-exactness: every lane of the plan pipeline is an independent
gather/compare chain over the same replicated arrays, so the sharded
result is identical — not approximately, bit-for-bit — to the
single-device result on the same queries (pinned by
tests/test_serve_lookup.py on all four surrogate datasets, and across
backends by tests/test_plan.py).  Pad lanes repeat the first real key
and are sliced off before completion.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import plan as plan_mod
from repro.dist import sharding as SH

#: Smallest dispatch width: keeps tiny deadline-flush batches from
#: compiling one program per size, and matches the 128-lane TPU register.
PAD_QUANTUM = 128


def make_plan(build, data_jnp, last_mile: Optional[str] = None):
    """Lower one index generation to its `LookupPlan`.

    ``last_mile`` defaults to the hyperparameter the index was built
    with, falling back to binary — same policy as the benchmarks.
    """
    return plan_mod.lower(build, data_jnp, last_mile=last_mile)


def data_axis_mesh(devices=None):
    """1-D mesh over ``devices`` (default: every local device), axis
    named `data`.  Its axis type is `Auto`: the jnp lookup's gathers from
    the replicated key array are partitioned by the compiler from the
    query sharding, which an `Explicit` axis (the default of
    `jax.make_mesh`) would refuse."""
    devices = list(jax.devices() if devices is None else devices)
    return Mesh(np.array(devices), ("data",), axis_types=(AxisType.Auto,))


class ShardedDispatcher:
    """Pads, places, and runs query batches over the data mesh axis."""

    def __init__(self, mesh=None, pad_quantum: int = PAD_QUANTUM,
                 recorder=None):
        self.mesh = data_axis_mesh() if mesh is None else mesh
        #: placement of everything a batch reads besides itself (key
        #: array, index state): one copy on every device of the mesh
        self.replicated = NamedSharding(self.mesh, P())
        self.pad_quantum = int(pad_quantum)
        # one rule walk for everyone: the dist layer owns the policy
        self.n_shards = SH.dispatch_groups(mesh=self.mesh,
                                           rules=SH.ACT_RULES)
        #: optional `repro.obs.trace.SpanRecorder`: the synchronous
        #: dispatch path splits into a pad+place span (host-side data
        #: movement) and a device span (launch + block), so a slow batch
        #: names which half it spent its time in.
        self.recorder = recorder
        # Pinned host staging buffers, one per pow2 bucket: padding into
        # a reused buffer instead of a fresh np.empty per batch.
        # Single-writer is guaranteed by the service's dispatch lock
        # (sync) / the executor's launch mutex (async); `pad_and_place`
        # blocks on the placement before returning, because the
        # host-to-device copy is asynchronous and the next batch reuses
        # the buffer.
        self._staging: dict = {}
        self.staging_hits = 0
        self.staging_allocs = 0

    def padded_size(self, m: int) -> int:
        """Next power-of-two >= max(m, quantum), then up to a multiple of
        the shard count — bounds distinct compiled shapes at log2(max)."""
        p = self.pad_quantum
        while p < m:
            p <<= 1
        r = p % self.n_shards
        return p + (self.n_shards - r if r else 0)

    def query_sharding(self, p: int):
        """The placement of a padded query batch — one rule walk through
        the dist layer (also what AOT executable lowering keys on)."""
        return SH.act_sharding((p,), ("batch",), self.mesh)

    def place_operands(self, ops):
        """Replicate a program's operand pytree over the mesh (a no-op
        for arrays already placed there)."""
        return jax.device_put(ops, self.replicated)

    def place(self, q_padded: np.ndarray):
        """Device-put one already-padded batch over the data axis."""
        q_padded = np.asarray(q_padded, dtype=np.uint64)
        return jax.device_put(jnp.asarray(q_padded),
                              self.query_sharding(q_padded.size))

    def pad_and_place(self, keys: np.ndarray):
        """Pad to the pow2 bucket and place on the mesh; returns
        ``(device batch, padded size)`` — the launch half of dispatch."""
        keys = np.asarray(keys, dtype=np.uint64)
        m = keys.size
        p = self.padded_size(m)
        if p != m:
            q = self._staging.get(p)
            if q is None:
                # Deliberately 64-byte-MISALIGNED view: XLA's CPU
                # zero-copy fast path aliases an owning, 64-byte-aligned
                # numpy array into the "device" buffer outright (x64
                # mode preserves uint64, so nothing forces a convert-
                # copy), and an aliased staging buffer corrupts every
                # in-flight batch the moment the next batch pads into
                # it.  Misalignment forces real copy semantics on every
                # placement.
                raw = np.empty(p + 8, np.uint64)
                off = 1 if raw.ctypes.data % 64 == 0 else 0
                q = raw[off:off + p]
                self._staging[p] = q
                self.staging_allocs += 1
            else:
                self.staging_hits += 1
            q[:m] = keys
            q[m:] = keys[0]  # any valid key: lanes are independent
        else:
            q = keys
        qj = self.place(q)
        if q is not keys:
            # The staging buffer is rewritten by the very next batch of
            # this bucket, but jax's host-to-device transfer reads the
            # host bytes ASYNCHRONOUSLY — returning before the copy has
            # happened lets batch N+1's pad overwrite batch N's queries
            # in flight (observed as a whole sub-batch answering for the
            # following batch).  Block on the placement: the wait is the
            # memcpy only, device compute still overlaps.
            jax.block_until_ready(qj)
        return qj, p

    @staticmethod
    def finalize(out, m: int, instrumented: bool = False):
        """Block on a launched computation and slice off the pad lanes —
        the completion half of dispatch (the only point that waits on
        the device, which is what the async executor overlaps).

        With ``instrumented``, ``out`` is ``(payload, packed stats)``:
        the payload is finalized recursively while the packed stats
        vector — already a fixed-size device reduction with pad lanes
        masked out on device — crosses to host in ONE transfer, never
        sliced.
        """
        if instrumented:
            payload, stats = out
            return (ShardedDispatcher.finalize(payload, m),
                    np.asarray(stats))
        if isinstance(out, tuple):
            return tuple(np.asarray(o)[:m] for o in out)
        return np.asarray(out, dtype=np.int64)[:m]

    def __call__(self, fn, keys: np.ndarray, backend: str = "jnp",
                 n_valid_arg: bool = False):
        """Run a plan (its ``backend`` lookup program, operands replicated
        over the mesh) or any lookup callable on `keys`, synchronously:
        launch then finalize.

        Returns int64 positions for plain lookups; executables that
        return a tuple (e.g. a plan's scan: positions + record window)
        come back as a tuple of host arrays, each sliced to the real
        batch size along axis 0.  ``n_valid_arg=True`` passes the real
        (pre-pad) batch size as a dynamic int32 scalar second argument —
        the instrumented-executable convention.
        """
        from repro.obs.trace import maybe_span

        if isinstance(fn, plan_mod.LookupPlan):
            fn = plan_mod.BoundProgram(
                fn.program("lb", backend=backend, mesh=self.mesh),
                self.place_operands(fn.operands(backend)))
        keys = np.asarray(keys, dtype=np.uint64)
        with maybe_span(self.recorder, "pad_place", cat="serve",
                        n_keys=int(keys.size)):
            qj, p = self.pad_and_place(keys)
        with maybe_span(self.recorder, "device", cat="serve",
                        padded=int(p), n_shards=self.n_shards):
            out = fn(qj, np.int32(keys.size)) if n_valid_arg else fn(qj)
            return self.finalize(out, keys.size,
                                 instrumented=n_valid_arg)


# ---------------------------------------------------------------------------
# Range-routed dispatch (DESIGN.md §16)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RoutedContext:
    """Everything one routed batch pins at dispatch time.

    ``lane_ctxs[s][r]`` is the executor context (read/scan programs, the
    operands placed on the lane's device, cache key) of replica ``r`` of
    shard ``s`` — the executable cache keys on ``(shard generation
    version, replica)``, so AOT executables stay committed to their
    lane's device.  Fields are intentionally
    untyped: the executor imports this class, not the other way round.
    """

    topology: Any                       # ShardTopology
    lane_ctxs: Tuple[Tuple[Any, ...], ...]
    offsets: Tuple[int, ...]
    versions: Tuple[int, ...]           # per-shard generation versions
    version: int                        # RoutedGeneration version
    instrumented: bool = False

    @property
    def key(self):
        """Executor slot identity — mirrors AsyncContext.key[0]."""
        return (self.version,)


class _RoutedHandle:
    """One launched routed batch: per-shard in-flight outputs plus the
    inverse permutation that restores admission order at finalize."""

    def __init__(self, subs, order, counts, padded, m, kind,
                 instrumented, rctx):
        self.subs = subs                # [(shard, lane out), ...]
        self.order = order              # admission index per sorted key
        self.counts = counts            # keys per shard (all shards)
        self.padded = padded            # summed per-shard padded sizes
        self.m = m
        self.kind = kind
        self.instrumented = instrumented
        self.rctx = rctx

    def finalize(self):
        """Block per shard, lift local ranks to global (``+ offsets[s]``),
        and gather through the inverse permutation — results come back in
        exact admission order, which is what keeps routed completion FIFO
        per request.  Returns ``(result, stats, padded)`` where ``stats``
        is a list of ``(shard generation version, packed stats)``.
        """
        offs = self.rctx.offsets
        starts = np.zeros(len(self.counts) + 1, dtype=np.int64)
        np.cumsum(self.counts, out=starts[1:])
        pos = np.empty(self.m, dtype=np.int64)
        win = None
        stats = []
        for s, out in self.subs:
            c = int(self.counts[s])
            fin = ShardedDispatcher.finalize(out, c, self.instrumented)
            if self.instrumented:
                fin, st = fin
                stats.append((self.rctx.versions[s], st))
            idx = self.order[starts[s]:starts[s] + c]
            if isinstance(fin, tuple):        # scan: (pos, window)
                if win is None:
                    win = np.empty((self.m,) + fin[1].shape[1:],
                                   fin[1].dtype)
                pos[idx] = np.asarray(fin[0], dtype=np.int64) + offs[s]
                win[idx] = fin[1]
            else:
                pos[idx] = fin + offs[s]
        if win is not None:
            return (pos, win), stats, self.padded
        return pos, stats, self.padded


class RoutedDispatcher:
    """Scatter/gather dispatch over range-partitioned shard lanes.

    One single-device `ShardedDispatcher` per (shard, replica) lane —
    each lane reuses the broadcast dispatcher's padding, staging, and
    placement machinery verbatim, just pinned to its own device.  The
    route step buckets each admitted key to its owning shard (host
    searchsorted at admission, or the device branchless upper bound via
    `ShardTopology.route_device`); per-shard sub-batches launch without
    blocking, and `_RoutedHandle.finalize` gathers them back into
    admission order.  Per-device work drops from O(batch) to
    O(batch/shards).
    """

    def __init__(self, topology, devices=None,
                 pad_quantum: int = PAD_QUANTUM, recorder=None):
        self.pad_quantum = int(pad_quantum)
        self.recorder = recorder
        self._rr_lock = threading.Lock()
        self.lanes_epoch = 0
        self._devices = list(devices) if devices is not None \
            else list(jax.devices())
        self._build_lanes(topology)

    def _build_lanes(self, topology):
        groups = SH.shard_replica_groups(self._devices, topology.replicas)
        self.lanes = tuple(
            tuple(ShardedDispatcher(
                mesh=data_axis_mesh([dev]),
                pad_quantum=self.pad_quantum, recorder=self.recorder)
                for dev in grp)
            for grp in groups)
        self._rr = [0] * len(groups)
        self.replicas = tuple(topology.replicas)

    def set_replicas(self, topology) -> bool:
        """Rebuild lanes when the shard/replica layout changes; bumps
        ``lanes_epoch`` so cached lane contexts are re-derived."""
        if (len(self.lanes) == topology.n_shards
                and self.replicas == tuple(topology.replicas)):
            return False
        self._build_lanes(topology)
        self.lanes_epoch += 1
        return True

    @property
    def n_shards(self) -> int:
        return len(self.lanes)

    def padded_size(self, m: int) -> int:
        """Worst-case single-lane bucket for warm planning (actual
        routed padding is per sub-batch)."""
        return self.lanes[0][0].padded_size(m)

    def _pick(self, s: int) -> int:
        """Round-robin read fan-out over shard ``s``'s replicas."""
        with self._rr_lock:
            r = self._rr[s]
            self._rr[s] = (r + 1) % len(self.lanes[s])
        return r

    @property
    def staging_allocs(self) -> int:
        return sum(d.staging_allocs for grp in self.lanes for d in grp)

    @property
    def staging_hits(self) -> int:
        return sum(d.staging_hits for grp in self.lanes for d in grp)

    @staticmethod
    def routes_for(group, topology):
        """Admission-time shard ids for a batch of requests, or None if
        any request missed the route step or was routed against a
        different (hot-swapped) topology — identity, not equality: a
        republish must force a re-route."""
        sids = []
        for req in group:
            route = getattr(req, "route", None)
            if route is None or route[0] is not topology:
                return None
            sids.append(route[1])
        return np.concatenate(sids) if sids else None

    def launch(self, rctx: RoutedContext, kind: str, aux: int,
               keys: np.ndarray, routes=None, exec_cache=None):
        """Scatter one admitted batch over its shard lanes; returns a
        `_RoutedHandle` (completion is the handle's ``finalize``).

        ``exec_cache`` (async path) resolves each lane's AOT executable;
        without it (sync path) the lane context's jitted programs run
        directly.  Either way the lane's operands (``bind``) follow the
        query.  Empty shards launch nothing.
        """
        from repro.obs.trace import maybe_span

        keys = np.asarray(keys, dtype=np.uint64)
        m = keys.size
        topo = rctx.topology
        instr = rctx.instrumented and kind != "scan"
        with maybe_span(self.recorder, "route", cat="serve",
                        n_keys=int(m), n_shards=self.n_shards):
            sid = routes if routes is not None else topo.route(keys)
            order = np.argsort(sid, kind="stable")
            counts = np.bincount(sid, minlength=self.n_shards)
            sorted_keys = keys[order]
        subs = []
        padded = 0
        start = 0
        for s in range(self.n_shards):
            c = int(counts[s])
            if c == 0:
                continue
            sub = sorted_keys[start:start + c]
            start += c
            r = self._pick(s)
            lane = self.lanes[s][r]
            ctx = rctx.lane_ctxs[s][r]
            qj, p = lane.pad_and_place(sub)
            padded += p
            make_fn = ((lambda c=ctx: c.read_fn) if kind != "scan"
                       else (lambda c=ctx, a=aux: c.scan_fn(int(a))))
            if exec_cache is not None:
                exe = exec_cache.get(ctx, kind, aux, p, make_fn, lane)
            else:
                exe = make_fn()
            args = (np.int32(c),) if instr else ()
            subs.append((s, exe(qj, *args, *ctx.bind)))
        return _RoutedHandle(subs, order, counts, padded, m, kind,
                             instr, rctx)

    def __call__(self, rctx: RoutedContext, kind: str, aux: int,
                 keys: np.ndarray, routes=None):
        """Synchronous routed dispatch: launch then finalize."""
        return self.launch(rctx, kind, aux, keys, routes=routes).finalize()
