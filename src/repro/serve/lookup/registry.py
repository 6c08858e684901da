"""Index generations with atomic hot-swap (DESIGN.md §9.3).

A `Generation` is one fully-built, immutable serving unit: the
`IndexBuild` (state pytree + interpreting functions), the device copy of
the sorted key array, the `LookupPlan` the build lowers to, and the
plan-compiled lookup for the generation's backend.  Where the key array
lives is the owning service's call (`IndexRegistry.placement`): on every
device of a broadcast mesh, or on the device of a routed shard's lane.
The registry's only mutable cell is a name -> Generation pointer; `publish` replaces that
pointer AFTER the build completes, so a reader can observe the old
generation or the new one, never a half-built one.  Swapping does not
drain in-flight batches: a dispatched batch pins the generation it was
taken with (`service._process_batch` reads `current()` exactly once per
batch via `_pin_context`; the mutable service re-pins per same-kind run
so reads observe earlier insert runs) and completes against it even if
a swap lands mid-batch.

Rebuilds (`build_and_publish`) run entirely outside the lock — index
construction is seconds of host-side numpy (benchmarks/build_times.csv)
and must never stall admission or dispatch.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import base
from repro.core import spec as spec_mod
from repro.core.plan import LookupPlan
from repro.obs.trace import maybe_span
from repro.serve.common import MonotonicCounter
from repro.serve.lookup.dispatch import make_plan
from repro.serve.lookup.topology import ShardTopology

DEFAULT_NAME = "default"


@dataclasses.dataclass(frozen=True)
class Generation:
    """One immutable, fully-built serving generation."""

    version: int
    build: base.IndexBuild
    data: Any                 # jnp device copy of the sorted keys
    plan: LookupPlan          # the build lowered to the plan IR
    fn: Callable              # plan-compiled lookup: queries -> positions
    n_keys: int
    #: The first key, read once when the generation is made: the valid
    #: key warm-up fills its dummy batches with.  A pin reads it from
    #: here and touches no device memory.
    sample_key: int
    backend: str = "jnp"      # plan backend this generation serves with
    #: The validated `IndexSpec` this generation was built from — the
    #: serializable address of the serving unit (hot-swap, sharded
    #: dispatch, and the services are spec-addressable through it).
    #: `spec.backend`/`spec.last_mile` always reflect what the
    #: generation actually serves with.
    spec: Optional[spec_mod.IndexSpec] = None
    #: Shard index inside a RoutedGeneration (None for broadcast
    #: generations) — threaded into per-shard health records.
    shard: Optional[int] = None
    #: operands placed per dispatcher placement (`operands_on`)
    _placed: dict = dataclasses.field(default_factory=dict, repr=False,
                                      compare=False)

    def program(self, kind: str, m: Optional[int] = None, mesh=None,
                donate: bool = False) -> Callable:
        """The plan's jitted program of one op kind on this generation's
        backend (`LookupPlan.program`); its last argument is
        `operands_on(...)`.  Shared by every generation of the same
        shape, so a hot-swap to one re-uses the compiled program."""
        return self.plan.program(kind, backend=self.backend, m=m,
                                 mesh=mesh, donate=donate)

    def operands_on(self, dispatcher) -> Any:
        """The plan's operands (key array + index state) placed the way
        ``dispatcher`` runs batches — replicated over its mesh — once per
        placement."""
        key = dispatcher.replicated
        ops = self._placed.get(key)
        if ops is None:
            ops = dispatcher.place_operands(
                self.plan.operands(self.backend))
            self._placed[key] = ops
        return ops


@dataclasses.dataclass(frozen=True, eq=False)
class RoutedGeneration:
    """One published *set* of per-shard generations plus the topology
    that routes into them (DESIGN.md §16).

    Swaps atomically as a unit: the registry pointer flips to the whole
    RoutedGeneration, so a pinned batch observes one consistent
    (topology, shard builds) pair even while a re-publish is in flight.
    Shard ``s`` serves keys in ``(split[s-1], split[s]]`` with its own
    (smaller, per-slice tuned) plan; the routed global rank is
    ``topology.offsets[s] + LB_local``.
    """

    version: int
    topology: ShardTopology
    shards: Tuple[Generation, ...]
    spec: Optional[spec_mod.IndexSpec] = None
    backend: str = "jnp"

    @property
    def n_keys(self) -> int:
        return self.topology.n_keys

    @property
    def shard_versions(self) -> Tuple[int, ...]:
        return tuple(s.version for s in self.shards)

    @property
    def plan(self) -> LookupPlan:
        """First shard's plan — shape/name probe only; never dispatch
        through it directly (it covers one key range)."""
        return self.shards[0].plan

    @property
    def point_only(self) -> bool:
        return any(s.plan.point_only for s in self.shards)

    @property
    def max_err(self) -> int:
        return max(s.plan.bounds.max_err for s in self.shards)

    @property
    def max_scan_len(self) -> int:
        """Largest exact routed scan width: a shard-s window is repaired
        with the first ``m`` records of shard s+1, which only covers the
        spill when every shard holds at least ``m`` keys."""
        return self.topology.min_shard_len

    def spill(self, s: int, length: int):
        """The head of shard ``s+1`` (None for the last shard): what a
        shard-s scan window is completed from (`LookupPlan.scan_expr`).
        All shard-s records sort strictly below all shard-(s+1) records
        (boundaries are snapped to duplicate runs), so the first ``m`` of
        the sorted union is exactly the global window."""
        if s == len(self.shards) - 1:
            return None
        return self.shards[s + 1].data[:length]


class IndexRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._versions = MonotonicCounter()
        self._current: Dict[str, Generation] = {}
        self._subscribers: list = []
        #: optional `repro.obs.trace.SpanRecorder` (set by the owning
        #: service): hot-swap builds and publish instants become
        #: lifecycle spans, so a latency blip during a swap is visually
        #: attributable in the exported trace.
        self.recorder = None
        #: optional `repro.obs.health.HealthMonitor` (set by the owning
        #: service): every publish opens a per-generation health record
        #: keyed by version, so stats from a batch that completes against
        #: a just-retired generation still land in ITS record.
        self.health = None
        #: optional ``(keys, shard, topology) -> device array`` (set by
        #: the owning service): where a new generation's key array goes.
        #: None keeps the default device.
        self.placement = None

    def place_keys(self, keys: np.ndarray, shard: Optional[int] = None,
                   topology: Optional[ShardTopology] = None):
        """Device copy of one generation's sorted keys (`placement`)."""
        if self.placement is None:
            return jnp.asarray(keys)
        return self.placement(keys, shard, topology)

    def subscribe(self, callback) -> None:
        """Register ``callback(name, generation)`` to run after every
        publish (outside the registry lock, on the publishing thread).
        The executable cache hangs its invalidation-on-swap here: the
        moment a new generation is visible, stale executables are
        evicted and a warm-up of the new generation can be scheduled.
        Callbacks must be cheap or hand off — a publish can come from a
        compaction thread holding its own locks."""
        with self._lock:
            self._subscribers.append(callback)

    def current(self, name: str = DEFAULT_NAME) -> Generation:
        with self._lock:
            gen = self._current.get(name)
        if gen is None:
            raise KeyError(f"no generation published under {name!r}")
        return gen

    def publish(self, build: base.IndexBuild, data,
                name: str = DEFAULT_NAME,
                last_mile: Optional[str] = None,
                backend: str = "jnp",
                spec: Optional[spec_mod.IndexSpec] = None,
                keys: Optional[np.ndarray] = None) -> Generation:
        """Lower a COMPLETE IndexBuild to its plan, wrap it into a
        generation, and swap it in.  ``spec`` defaults to the spec the
        build carries (`spec.build` stamps it into ``meta``) and is
        re-aligned to the backend/last-mile the generation serves with.
        ``keys`` is the host copy of ``data`` (`make_generation`)."""
        gen = self.make_generation(build, data, last_mile=last_mile,
                                   backend=backend, spec=spec, keys=keys)
        with self._lock:
            self._current[name] = gen
            subscribers = list(self._subscribers)
        if self.health is not None:
            self.health.on_publish(gen)
        if self.recorder is not None:
            self.recorder.instant("publish", cat="lifecycle", reg_name=name,
                                  version=gen.version, index=gen.plan.name,
                                  n_keys=gen.n_keys)
        for cb in subscribers:
            cb(name, gen)
        return gen

    def publish_prebuilt(self, gen: Generation,
                         name: str = DEFAULT_NAME) -> Generation:
        """Swap in a Generation made earlier with `make_generation` —
        the autotune retuner's path (DESIGN.md §17): the candidate is
        compiled and oracle-VERIFIED off the hot path first, and the
        very object that passed verification is what goes live
        (publish-after-verify, never rebuild-after-verify).  Same
        health/trace/subscriber fan-out as `publish`."""
        with self._lock:
            self._current[name] = gen
            subscribers = list(self._subscribers)
        if self.health is not None:
            self.health.on_publish(gen)
        if self.recorder is not None:
            self.recorder.instant("publish", cat="lifecycle", reg_name=name,
                                  version=gen.version, index=gen.plan.name,
                                  n_keys=gen.n_keys)
        for cb in subscribers:
            cb(name, gen)
        return gen

    def make_generation(self, build: base.IndexBuild, data,
                        last_mile: Optional[str] = None,
                        backend: str = "jnp",
                        spec: Optional[spec_mod.IndexSpec] = None,
                        shard: Optional[int] = None,
                        keys: Optional[np.ndarray] = None) -> Generation:
        """Lower a build to a versioned Generation WITHOUT publishing it
        — the routed publish path assembles several of these and swaps
        them in as one unit.  Reads the first key off the device once,
        as the generation's `sample_key`.

        Where the plan serves through a fused kernel executor, its state
        is prepared here, in a ``fused_prepare`` lifecycle span, from
        ``keys``: the host copy of ``data`` the build was given (without
        it the plan reads the key array back off the device).  The
        build's meta gains ``served_window``, the error-bound window the
        served lookup searches, and ``kernel_serves``, 1 where the
        Pallas last-mile kernel serves and 0 where its exact jnp
        fallback (or the jnp backend) does."""
        plan = make_plan(build, data, last_mile=last_mile)
        if plan.uses_fused(backend):
            with maybe_span(self.recorder, "fused_prepare", cat="lifecycle",
                            n=int(plan.n),
                            branching=int(plan.meta.get("branching", 0))
                            ) as args:
                plan.fused_state(keys)
                if args is not None:
                    args["served_window"] = plan.served_window(backend)
        build.meta["served_window"] = plan.served_window(backend)
        build.meta["kernel_serves"] = int(plan.kernel_serves(backend))
        if spec is None:
            spec = build.meta.get("spec")
        if spec is not None:
            spec = spec.replace(backend=backend,
                                last_mile=last_mile if last_mile is not None
                                else spec.last_mile)
        return Generation(
            version=self._versions.next(),
            build=build,
            data=data,
            plan=plan,
            fn=plan.compile(backend=backend),
            n_keys=int(data.shape[0]),
            sample_key=int(np.asarray(data[:1])[0]),
            backend=backend,
            spec=spec,
            shard=shard,
        )

    def publish_routed(self, shard_gens, topology: ShardTopology,
                       name: str = DEFAULT_NAME,
                       spec: Optional[spec_mod.IndexSpec] = None,
                       backend: str = "jnp") -> RoutedGeneration:
        """Swap a complete shard set in as one RoutedGeneration."""
        rgen = RoutedGeneration(
            version=self._versions.next(),
            topology=topology,
            shards=tuple(shard_gens),
            spec=spec,
            backend=backend,
        )
        with self._lock:
            self._current[name] = rgen
            subscribers = list(self._subscribers)
        if self.health is not None:
            self.health.on_publish_group(rgen.shards)
        if self.recorder is not None:
            self.recorder.instant(
                "publish", cat="lifecycle", reg_name=name,
                version=rgen.version, index=rgen.plan.name,
                n_keys=rgen.n_keys, n_shards=topology.n_shards)
        for cb in subscribers:
            cb(name, rgen)
        return rgen

    def build_and_publish_routed(self, index, keys: np.ndarray,
                                 topology: ShardTopology,
                                 hyper: Optional[Dict[str, Any]] = None,
                                 name: str = DEFAULT_NAME,
                                 last_mile: Optional[str] = None,
                                 backend: Optional[str] = None,
                                 tuner: Optional[spec_mod.Tuner] = None
                                 ) -> RoutedGeneration:
        """Build one generation per topology range and swap the set in.

        With a ``tuner``, each shard's spec is searched against ONLY its
        slice (per-shard byte budget = total / shards); without one,
        every shard reuses the coerced spec — smaller slices still give
        tighter error bounds for the same hyperparameters.
        """
        sp = spec_mod.coerce(index, hyper, backend=backend,
                             last_mile=last_mile)
        keys = np.asarray(keys, dtype=np.uint64)
        offs = topology.offsets
        shard_specs = [sp] * topology.n_shards
        builds = [None] * topology.n_shards
        if tuner is not None:
            results = tuner.tune_shards(keys, offs)
            shard_specs = [r.spec for r in results]
            builds = [r.build for r in results]
        gens = []
        with maybe_span(self.recorder, "index_build", cat="lifecycle",
                        reg_name=name, index=sp.index,
                        n_keys=int(keys.size),
                        n_shards=topology.n_shards):
            for s in range(topology.n_shards):
                sl = keys[offs[s]:offs[s + 1]]
                b = builds[s] if builds[s] is not None \
                    else spec_mod.build(shard_specs[s], sl)
                gens.append(self.make_generation(
                    b, self.place_keys(sl, shard=s, topology=topology),
                    last_mile=shard_specs[s].last_mile,
                    backend=shard_specs[s].backend,
                    spec=shard_specs[s], shard=s, keys=sl))
        return self.publish_routed(gens, topology, name=name, spec=sp,
                                   backend=sp.backend)

    def build_and_publish(self, index, keys: np.ndarray,
                          hyper: Optional[Dict[str, Any]] = None,
                          name: str = DEFAULT_NAME,
                          last_mile: Optional[str] = None,
                          backend: Optional[str] = None) -> Generation:
        """Rebuild on a fresh key set, then swap — build is outside the
        lock, the swap is one pointer assignment.

        ``index`` is an `IndexSpec` (the declarative path — DESIGN.md
        §12; ``hyper`` must then be None and explicit ``last_mile``/
        ``backend`` args override the spec's) or a registry name with a
        ``hyper`` dict (legacy callers), which is folded into a
        validated spec so every build runs through `spec.build`.
        """
        sp = spec_mod.coerce(index, hyper, backend=backend,
                             last_mile=last_mile)
        keys = np.asarray(keys, dtype=np.uint64)
        with maybe_span(self.recorder, "index_build", cat="lifecycle",
                        reg_name=name, index=sp.index, n_keys=int(keys.size)):
            build = spec_mod.build(sp, keys)
            data = self.place_keys(keys)
        return self.publish(build, data, name=name, last_mile=sp.last_mile,
                            backend=sp.backend, spec=sp, keys=keys)

    def health_records(self, window_s: float = 10.0) -> list:
        """Per-generation health records (empty when no monitor is
        attached) — the registry-facing view `/health.json` exports."""
        if self.health is None:
            return []
        return self.health.records(window_s)
