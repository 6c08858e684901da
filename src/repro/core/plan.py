"""`LookupPlan` IR: one lowering target for every index (DESIGN.md §11).

The paper's central observation (§5) is that every competitive index —
learned or not — reduces to the same two-phase shape: *predict a
position, then bounded last-mile search*.  This module makes that shape
an explicit, inspectable value instead of a per-index closure:

    IndexBuild --lower()--> LookupPlan(bounds, data, last_mile)
                                |.program(kind, backend)   -> (q, ..., ops) -> result
                                |.operands(backend)        -> ops: keys + index state
                                |.compile(backend)         -> q -> LB ranks
                                |.compile_scan(m)          -> q -> (LB, window)
                                |.compile_merged()         -> (q, delta) -> merged LB
                                |.compile_instrumented()   -> (q, n_valid) -> (LB, health stats)
                                |.compile_instrumented_merged()
                                                           -> (q, n_valid, delta) -> (LB, stats)

A program takes the sorted keys and the index state as its LAST argument
(the ``ops`` pytree), never as closure constants: the compiled program
holds no key data (at SOSD's 200M keys that would be 1.6 GB baked into
every program), and one program per op kind and batch bucket serves
every generation of the same shape.  The ``compile*`` entry points bind
a plan's own operands for direct calls.

A plan is a `bounds` stage — the index's state pytree, a pure predict
function ``(state, q) -> (lo, hi)`` with ``hi`` inclusive, and the
static window bound ``max_err`` (``hi - lo + 1 <= max_err`` with
``LB in [lo, hi]``) — composed with a last-mile stage executed by a
pluggable backend:

  ``"jnp"``     the vectorized `repro.core.search.SEARCH_FNS` searches,
                bit-identical to the historical fused pipeline;
  ``"pallas"``  the `kernels/bounded_search` kernel consuming the plan's
                bounds (any index), or — where an index registers one —
                a fused whole-plan kernel executor (`kernels/rmi_lookup`
                for RMI).  Off the TPU the kernels run in interpret mode,
                so both backends execute everywhere; under a batch
                sharded over several devices they run per device
                (`jax.shard_map`).

Both backends return the exact lower-bound rank, so they are
bit-identical for every plan (pinned by tests/test_plan.py across the
full index x dataset x last-mile matrix).

Every consumer goes through plans: `core.search.fused_lookup_fn` is a
thin ``lower(...).compile(...)`` shim, the serving registry publishes
`Generation`s carrying their plan, the mutable layer's delta rank
correction and the range-scan materialization are plan transforms
(`compile_merged*`), and the benchmark matrix selects backends through
the same seam (`benchmarks/_common.full_lookup_fn`).
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Any, Callable, Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import base, search
from repro.obs.health import HEALTH_DISP_BUCKETS, HEALTH_TRAFFIC_BUCKETS

__all__ = ["BACKENDS", "BoundProgram", "BoundsStage", "FusedLowering",
           "LookupPlan",
           "health_stats_expr", "lower", "pack_health_stats",
           "register_fused", "FUSED_LOWERERS"]

#: The backend axis every lookup consumer can select on.
BACKENDS = ("jnp", "pallas")

#: index name -> `FusedLowering`.  A fused executor replaces the whole
#: predict+search pipeline with one kernel path; registered per index
#: family, used by backend="pallas".
FUSED_LOWERERS: Dict[str, "FusedLowering"] = {}


def register_fused(name: str, lookup: Callable):
    """Register ``prepare(plan) -> state`` (the decorated function) with
    its pure ``lookup(state, data, q, interpret, planes)`` for one index
    family."""
    def deco(prepare):
        FUSED_LOWERERS[name] = FusedLowering(prepare=prepare, lookup=lookup)
        return prepare

    return deco


@dataclasses.dataclass(frozen=True)
class BoundsStage:
    """The predict half of a plan.

    ``predict(state, q) -> (lo, hi)`` must be pure jnp (jit/shard-safe),
    with ``hi`` inclusive, ``lo <= LB(q) <= hi`` for every uint64 query
    (the §2 validity contract), and ``hi - lo + 1 <= max_err`` with
    ``max_err`` static — the error guarantee that fixes last-mile trip
    counts and kernel window widths.  Point-only indexes (robin_hash)
    instead return ``(found, pos)`` and set ``max_err = 0``.
    """

    state: Any
    predict: Callable[[Any, base.Array], base.SearchBound]
    max_err: int


def _window_gather(data, pos, m: int):
    """[B] start positions -> [B, m] record window, one static gather.

    Past-the-end lanes hold the dtype's max value (for uint64 keys:
    UINT64_MAX, the same sentinel the delta buffer pads with) so windows
    of different plans merge by plain sort.
    """
    n = data.shape[0]
    sentinel = jnp.asarray(jnp.iinfo(data.dtype).max, data.dtype)
    idx = pos[:, None] + jnp.arange(m, dtype=pos.dtype)[None, :]
    oob = idx >= n
    window = jnp.take(data, jnp.clip(idx, 0, n - 1), mode="clip")
    return jnp.where(oob, sentinel, window)


def _cum_bucket_hist(vals, edges, valid):
    """Bucket counts WITHOUT a scatter: count ``vals >= edge`` per edge
    (a [B, E] comparison reduced over lanes), then difference the
    cumulative counts.  Identical integer counts to ``.at[idx].add`` —
    XLA lowers the comparisons to vector code where a CPU/TPU scatter
    serializes — and invalid lanes are masked out of every column."""
    c = jnp.sum((vals[:, None] >= edges[None, :]) & valid[:, None],
                axis=0, dtype=jnp.int32)
    total = jnp.sum(valid, dtype=jnp.int32)
    cext = jnp.concatenate([total[None], c, jnp.zeros(1, jnp.int32)])
    return cext[:-1] - cext[1:]


def health_stats_expr(pos, lo, hi, n: int, max_err: int, n_valid,
                      point_only: bool = False):
    """Fixed-size device reductions for the health monitor (DESIGN.md §15).

    ``pos`` is the [B] int64 result lanes, ``(lo, hi)`` the bounds-stage
    window (ignored when ``point_only``), ``n_valid`` a dynamic int32
    scalar masking out pad lanes so dispatcher padding never pollutes the
    statistics.  Everything returned is O(buckets): a log2
    prediction-displacement histogram (bucket 0 = exact hit, bucket j =
    ``[2^(j-1), 2^j)``, last bucket overflows — `obs.health` owns the
    geometry), a rank-quantized traffic histogram (bucket ``r*K//n``,
    realized as cumulative counts against the ceil rank edges — the
    same integer partition), and scalar sums for mean displacement /
    bound width / last-mile steps.  Displacement, width, and rank are
    narrowed to int32 when ``n`` permits — they are bounded by ``n`` —
    which halves the comparison bandwidth on the hot path.
    """
    B = pos.shape[0]
    K = HEALTH_TRAFFIC_BUCKETS
    lane = jnp.arange(B, dtype=jnp.int32) < n_valid
    dt = jnp.int32 if int(n) < 2 ** 31 else jnp.int64
    if point_only:
        valid = lane & (pos >= 0)
        disp = jnp.zeros(B, dt)
        width = jnp.where(valid, 1, 0).astype(dt)
        steps = jnp.zeros(B, dt)
    else:
        valid = lane
        lo_n, hi_n = lo.astype(dt), hi.astype(dt)
        mid = lo_n + (hi_n - lo_n) // 2
        disp = jnp.where(valid, jnp.abs(pos.astype(dt) - mid), 0)
        width = jnp.where(valid, hi_n - lo_n + 1, 0)
        # binary-search trip count over the bound: ceil(log2(width))
        s_edges = jnp.asarray(
            [1 << j for j in range(max(1, int(max_err).bit_length()))], dt)
        steps = jnp.where(
            valid,
            jnp.sum(width[:, None] > s_edges[None, :], axis=1,
                    dtype=jnp.int32), 0).astype(dt)
    d_edges = jnp.asarray(
        [1 << j for j in range(HEALTH_DISP_BUCKETS - 1)], dt)
    disp_hist = _cum_bucket_hist(disp, d_edges, valid)
    rank = jnp.clip(pos, 0, n - 1).astype(dt)
    # rank r is in traffic bucket r*K//n  <=>  r >= ceil(j*n/K) for
    # exactly (bucket index + 1) edges j — cumulative form of the same
    # partition
    t_edges = jnp.asarray(
        [(j * int(n) + K - 1) // K for j in range(1, K)], dt)
    traffic_hist = _cum_bucket_hist(rank, t_edges, valid)
    return {
        "n": jnp.sum(valid.astype(jnp.int32)),
        "disp_hist": disp_hist,
        "traffic_hist": traffic_hist,
        "disp_sum": jnp.sum(disp.astype(jnp.int64)),
        "disp_max": jnp.max(disp).astype(jnp.int64),
        "width_sum": jnp.sum(width.astype(jnp.int64)),
        "steps_sum": jnp.sum(steps.astype(jnp.int64)),
    }


def pack_health_stats(stats) -> Any:
    """Flatten one stats dict to a single int64 vector (the layout
    `repro.obs.health.unpack_stats` reverses): 5 scalars, then the two
    histograms.  One device array per batch means ONE host transfer in
    the completion path instead of seven."""
    scalars = jnp.stack([
        stats["n"].astype(jnp.int64), stats["disp_sum"],
        stats["disp_max"], stats["width_sum"], stats["steps_sum"]])
    return jnp.concatenate([scalars,
                            stats["disp_hist"].astype(jnp.int64),
                            stats["traffic_hist"].astype(jnp.int64)])


def _value_key(v):
    """Hashable stand-in for one closure value of a predict function, or
    None when the value has no structural identity (e.g. an array)."""
    if v is None or isinstance(v, (bool, int, float, str, np.generic,
                                   np.dtype)):
        return (type(v), v)
    if isinstance(v, (tuple, list)):
        keys = tuple(_value_key(x) for x in v)
        return None if any(k is None for k in keys) else (type(v), keys)
    if callable(v) and hasattr(v, "__code__"):
        return _fn_key(v)
    return None


def _fn_key(fn):
    """Structural identity of a predict function: its code plus the values
    it closes over.  Two generations built with the same hyperparameters
    over key sets of the same size close over equal scalars, so they share
    one compiled program; anything else falls back to object identity
    (the program cache then holds the function, so the id stays unique)."""
    code = getattr(fn, "__code__", None)
    if code is None:
        return ("id", id(fn))
    cells = tuple(_value_key(c.cell_contents) for c in fn.__closure__ or ())
    if any(k is None for k in cells):
        return ("id", id(fn))
    return (code, cells)


@dataclasses.dataclass(frozen=True)
class FusedLowering:
    """A whole-plan kernel executor registered for one index family:
    ``prepare(plan)`` builds its device state once per plan, and
    ``lookup(state, data, q, interpret, planes)`` is the pure expression
    the programs run."""

    prepare: Callable
    lookup: Callable


def _width_class(max_err: int) -> int:
    """The static window bound programs are built with: ``max_err``
    rounded up to a power of two.  Any bound no tighter than the index's
    own is still a valid bound, so results are unchanged (trip counts
    and kernel windows are powers of two anyway), and generations whose
    error bounds differ within a power of two share one program."""
    max_err = int(max_err)
    return 0 if max_err <= 0 else 1 << (max_err - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class _Statics:
    """Everything a plan's programs depend on besides their operands."""

    predict: Callable
    max_err: int
    n: int
    last_mile: str
    point_only: bool
    fused: Optional[FusedLowering]


#: Process-wide program cache: (plan signature, kind, backend, ...) ->
#: jitted program.  Bounded LRU; an evicted program is rebuilt on demand.
_PROGRAMS: "collections.OrderedDict" = collections.OrderedDict()
_PROGRAMS_MAX = 512
_PROGRAMS_MU = threading.Lock()


def _per_device(run, mesh):
    """Run ``run(q, ops)`` on each device's slice of a batch sharded over
    every axis of ``mesh``, with the operands replicated.  A Mosaic kernel
    cannot be partitioned by the compiler, so sharded Pallas lookups go
    through here; lanes are independent, so the result is unchanged."""
    if mesh is None:
        return run
    batch = P(tuple(mesh.axis_names))
    return jax.shard_map(run, mesh=mesh, in_specs=(batch, P()),
                         out_specs=batch, check_vma=False)


class BoundProgram:
    """A program with its operands bound: ``bound(*args) ==
    program(*args, operands)`` — the convenience face of a plan."""

    def __init__(self, program, ops):
        self.program = program
        self.ops = ops

    def __call__(self, *args):
        return self.program(*args, self.ops)


@dataclasses.dataclass(frozen=True, eq=False)
class LookupPlan:
    """One index lowered to predict -> bounded-search, backend-agnostic.

    Programs take the key array and the index state as ARGUMENTS — the
    trailing ``ops`` pytree of `operands` — never as closure constants:
    a compiled program holds no key data, and one program per (kind,
    backend, batch bucket) serves every plan with the same `signature`.
    """

    name: str
    bounds: BoundsStage
    data: Any                  # jnp device copy of the sorted keys
    n: int
    last_mile: str = "binary"
    point_only: bool = False
    fused: Optional[FusedLowering] = None   # whole-plan kernel executor
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # per-plan cache: operands, bound callables, derived kernel state
    _cache: Dict[Any, Any] = dataclasses.field(
        default_factory=dict, repr=False)

    # -- statics and operands ----------------------------------------------
    @property
    def statics(self) -> _Statics:
        return _Statics(self.bounds.predict,
                        _width_class(self.bounds.max_err), int(self.n),
                        self.last_mile, self.point_only, self.fused)

    @property
    def signature(self):
        """Hashable identity of everything the programs close over."""
        sig = self._cache.get("signature")
        if sig is None:
            sig = (self.name, int(self.n), self.statics.max_err,
                   self.last_mile, self.point_only, self.fused,
                   _fn_key(self.bounds.predict))
            self._cache["signature"] = sig
        return sig

    def _use_fused(self, backend: str, fused: Optional[bool]) -> bool:
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
        if backend != "pallas" or self.point_only:
            return False
        if fused is None:
            return self.fused is not None
        if fused and self.fused is None:
            raise ValueError(f"plan {self.name!r} has no fused kernel executor")
        return bool(fused)

    def kernel_serves(self, backend: str = "jnp",
                      fused: Optional[bool] = None) -> bool:
        """Whether the Pallas last-mile kernel (not its exact jnp
        fallback for windows wider than one data tile) serves lookups;
        False where no kernel runs (jnp backend, point-only plans)."""
        from repro.kernels.bounded_search.ops import kernel_serves

        if backend != "pallas" or self.point_only:
            return False
        if self._use_fused(backend, fused):
            width = self.operands(backend, True)["fused"].max_err
        else:
            width = self.bounds.max_err
        return kernel_serves(int(width))

    def operands(self, backend: str = "jnp",
                 fused: Optional[bool] = None) -> Dict[str, Any]:
        """The device pytree every program of this backend takes last:
        the sorted keys, the index state and, for the Pallas backend, the
        kernel's data planes and the fused executor's state.  Built once
        per plan."""
        fused = self._use_fused(backend, fused)
        key = ("ops", backend, fused)
        ops = self._cache.get(key)
        if ops is not None:
            return ops
        ops = {"data": self.data, "state": self.bounds.state}
        if fused:
            ops["fused"] = self.fused.prepare(self)
        if backend == "pallas" and not self.point_only:
            from repro.kernels.bounded_search import ops as bs

            width = (ops["fused"].max_err if fused else self.bounds.max_err)
            if bs.kernel_serves(int(width)):
                ops["planes"] = jax.jit(bs.kernel_planes)(self.data)
        self._cache[key] = ops
        return ops

    # -- expression builders (pure, over (q, ..., ops)) ---------------------
    def _lb_run(self, backend, interpret, mesh):
        return _per_device(_lb_expr(self.statics, backend,
                                    self._use_fused(backend, None),
                                    interpret), mesh)

    def merged_expr(self, backend: str = "jnp",
                    interpret: Optional[bool] = None,
                    mesh=None) -> Callable:
        """Delta rank correction as a plan transform (DESIGN.md §10.2):
        ``(q, delta_padded, ops) -> LB_base(q) + LB_delta(q)``.  Exact
        because base and delta are disjoint sorted sets; the padded
        delta's UINT64_MAX sentinels can never be counted by a lower
        bound."""
        run = self._lb_run(backend, interpret, mesh)

        def merged(q, delta_padded, ops):
            lb_delta = jnp.searchsorted(delta_padded, q, side="left")
            return run(q, ops) + lb_delta.astype(jnp.int64)

        return merged

    def scan_expr(self, m: int, backend: str = "jnp",
                  interpret: Optional[bool] = None, mesh=None) -> Callable:
        """Range-scan materialization: ``(q, ops) -> (LB, window[B, m])``
        — the ``m`` records from ``LB(q)`` as one static-width windowed
        gather.  Where ``ops`` carries a ``spill`` (the head of the next
        key range of a routed topology), the window is completed from it:
        the first ``m`` of the sorted union, the same argument as the
        delta merged scan."""
        if self.point_only:
            raise ValueError(f"{self.name!r} is point-only: no scans")
        run = self._lb_run(backend, interpret, mesh)

        def scan(q, ops):
            pos = run(q, ops)
            window = _window_gather(ops["data"], pos, m)
            if "spill" in ops:
                spill = jnp.broadcast_to(ops["spill"][None, :m],
                                         (q.shape[0], m))
                window = jnp.sort(jnp.concatenate([window, spill], axis=1),
                                  axis=1)[:, :m]
            return pos, window

        return scan

    def merged_scan_expr(self, m: int, backend: str = "jnp",
                         interpret: Optional[bool] = None,
                         mesh=None) -> Callable:
        """Scan over the merged (base + delta) view: gather ``m`` from each
        side and keep the first ``m`` of their sorted union — exact because
        the merged array's next ``m`` records are contained in the union of
        the two windows, and both pad with the UINT64_MAX sentinel."""
        if self.point_only:
            raise ValueError(f"{self.name!r} is point-only: no scans")
        run = self._lb_run(backend, interpret, mesh)

        def scan(q, delta_padded, ops):
            pos_b = run(q, ops)
            pos_d = jnp.searchsorted(
                delta_padded, q, side="left").astype(jnp.int64)
            wb = _window_gather(ops["data"], pos_b, m).astype(
                delta_padded.dtype)
            wd = _window_gather(delta_padded, pos_d, m)
            window = jnp.sort(
                jnp.concatenate([wb, wd], axis=-1), axis=-1)[:, :m]
            return pos_b + pos_d, window

        return scan

    def _instr_base_expr(self, backend: str, interpret: Optional[bool],
                         mesh=None) -> Callable:
        """``(q, ops) -> (LB, lo, hi)`` sharing ONE predict between the
        search and the stats on the generic jnp path (the fused / pallas
        paths keep their own lookup and pay a second jnp predict for the
        stats — still backend-invariant by construction)."""
        predict = self.bounds.predict
        if backend == "jnp":
            fn = search.SEARCH_FNS[self.last_mile]
            max_err = self.statics.max_err

            def base_jnp(q, ops):
                lo, hi = predict(ops["state"], q)
                pos = fn(ops["data"], q, lo, hi, max_err).astype(jnp.int64)
                return pos, lo, hi

            return base_jnp

        run = self._lb_run(backend, interpret, mesh)

        def base_other(q, ops):
            lo, hi = predict(ops["state"], q)
            return run(q, ops), lo, hi

        return base_other

    def instrumented_expr(self, backend: str = "jnp",
                          interpret: Optional[bool] = None,
                          mesh=None) -> Callable:
        """``(q, n_valid, ops) -> (LB, packed stats)``: the lookup plus the
        `health_stats_expr` reduction flattened by `pack_health_stats`.

        The positions come from the SAME ops as the uninstrumented
        path — bit-identity holds by construction on every backend; the
        stats derive from the plan's own jnp bounds (not a fused
        kernel's refit state), so they are backend-invariant too.
        ``n_valid`` is a dynamic int32 scalar so one compiled program
        serves every occupancy of a padded batch bucket.
        """
        n, max_err = self.n, self.statics.max_err
        if self.point_only:
            run = self._lb_run(backend, interpret, mesh)

            def run_point_instr(q, n_valid, ops):
                pos = run(q, ops)
                stats = health_stats_expr(
                    pos, None, None, n, max_err, n_valid, point_only=True)
                return pos, pack_health_stats(stats)

            return run_point_instr

        base = self._instr_base_expr(backend, interpret, mesh)

        def run_instr(q, n_valid, ops):
            pos, lo, hi = base(q, ops)
            stats = health_stats_expr(pos, lo, hi, n, max_err, n_valid)
            return pos, pack_health_stats(stats)

        return run_instr

    def instrumented_merged_expr(self, backend: str = "jnp",
                                 interpret: Optional[bool] = None,
                                 mesh=None) -> Callable:
        """``(q, n_valid, delta_padded, ops) -> (merged LB, packed
        stats)``.  Stats describe the BASE plan (its model is what health
        tracks); the payload is exactly `merged_expr`'s rank."""
        if self.point_only:
            raise ValueError(
                f"{self.name!r} is point-only: no merged lookups")
        base = self._instr_base_expr(backend, interpret, mesh)
        n, max_err = self.n, self.statics.max_err

        def merged_instr(q, n_valid, delta_padded, ops):
            lb_base, lo, hi = base(q, ops)
            lb_delta = jnp.searchsorted(delta_padded, q, side="left")
            stats = health_stats_expr(lb_base, lo, hi, n, max_err, n_valid)
            return (lb_base + lb_delta.astype(jnp.int64),
                    pack_health_stats(stats))

        return merged_instr

    # -- programs (shared across plans) and bound entry points -------------
    def program(self, kind: str, backend: str = "jnp",
                interpret: Optional[bool] = None,
                fused: Optional[bool] = None, m: Optional[int] = None,
                donate: bool = False, mesh=None) -> Callable:
        """The jitted program of one op kind; ``ops`` is its last argument:

          ``"lb"``            ``(q, ops) -> LB``
          ``"instr"``         ``(q, n_valid, ops) -> (LB, stats)``
          ``"merged"``        ``(q, delta, ops) -> LB``
          ``"instr_merged"``  ``(q, n_valid, delta, ops) -> (LB, stats)``
          ``"scan"``          ``(q, ops) -> (LB, window[B, m])``
          ``"merged_scan"``   ``(q, delta, ops) -> (LB, window[B, m])``

        Cached process-wide by `signature`, so every generation of the
        same shape reuses one program.  ``mesh`` is the mesh a batch is
        sharded over: Pallas lookups then run per device (`jax.shard_map`).
        ``donate`` donates the query buffer (safe on the dispatcher's
        fresh placements).
        """
        # only the plain lookup can pick the generic path over a fused one
        fused = self._use_fused(backend, fused if kind == "lb" else None)
        if mesh is not None and backend != "pallas":
            mesh = None            # the jnp path partitions automatically
        key = (self.signature, kind, backend, interpret, fused,
               None if m is None else int(m), bool(donate), mesh)
        with _PROGRAMS_MU:
            fn = _PROGRAMS.get(key)
            if fn is not None:
                _PROGRAMS.move_to_end(key)
                return fn
        fn = jax.jit(self._program_expr(kind, backend, interpret, fused, m,
                                        mesh),
                     donate_argnums=(0,) if donate else ())
        with _PROGRAMS_MU:
            fn = _PROGRAMS.setdefault(key, fn)
            while len(_PROGRAMS) > _PROGRAMS_MAX:
                _PROGRAMS.popitem(last=False)
        return fn

    def _program_expr(self, kind, backend, interpret, fused, m, mesh):
        # expressions close over the plan's statics only (never `self`),
        # so a cached program holds no key array alive
        if kind == "lb":
            return _per_device(_lb_expr(self.statics, backend, fused,
                                        interpret), mesh)
        if kind == "instr":
            return self.instrumented_expr(backend, interpret, mesh)
        if kind == "merged":
            return self.merged_expr(backend, interpret, mesh)
        if kind == "instr_merged":
            return self.instrumented_merged_expr(backend, interpret, mesh)
        if kind == "scan":
            return self.scan_expr(int(m), backend, interpret, mesh)
        if kind == "merged_scan":
            return self.merged_scan_expr(int(m), backend, interpret, mesh)
        raise ValueError(f"unknown program kind {kind!r}")

    def _bound(self, kind, backend, interpret, fused=None, m=None,
               donate=False) -> Callable:
        fused = self._use_fused(backend, fused)
        key = ("bound", kind, backend, interpret, fused, m, donate)
        fn = self._cache.get(key)
        if fn is None:
            fn = BoundProgram(
                self.program(kind, backend, interpret, fused, m, donate),
                self.operands(backend, fused))
            self._cache[key] = fn
        return fn

    def compile(self, backend: str = "jnp", interpret: Optional[bool] = None,
                fused: Optional[bool] = None,
                donate: bool = False) -> Callable:
        """``q -> int64 LB ranks`` with this plan's operands bound (the
        canonical fused lookup).  ``donate=True`` donates the query
        buffer to XLA — safe when the caller stages each batch into a
        fresh device placement (the dispatcher does)."""
        return self._bound("lb", backend, interpret, fused, donate=donate)

    def compile_merged(self, backend: str = "jnp",
                       interpret: Optional[bool] = None) -> Callable:
        """``(q, delta) -> merged LB`` with this plan's operands bound."""
        return self._bound("merged", backend, interpret)

    def compile_scan(self, m: int, backend: str = "jnp",
                     interpret: Optional[bool] = None) -> Callable:
        """``q -> (LB, window)`` with this plan's operands bound."""
        return self._bound("scan", backend, interpret, m=int(m))

    def compile_instrumented(self, backend: str = "jnp",
                             interpret: Optional[bool] = None,
                             donate: bool = False) -> Callable:
        """``(q, n_valid) -> (LB, stats)`` with this plan's operands bound."""
        return self._bound("instr", backend, interpret, donate=donate)

    def compile_instrumented_merged(self, backend: str = "jnp",
                                    interpret: Optional[bool] = None
                                    ) -> Callable:
        """``(q, n_valid, delta) -> (merged LB, stats)``, operands bound."""
        return self._bound("instr_merged", backend, interpret)

    def build_displacement_quantile(self, q: float = 0.99,
                                    sample: int = 65536) -> float:
        """Displacement quantile of the plan's OWN keys: the build-time
        prediction error level that live traffic is compared against
        (the `disp_p99_ratio` health key).  For key ``keys[i]`` the true
        rank is ``i``, so displacement is ``|i - mid(predict(keys[i]))|``
        — evaluated over an evenly strided sample of up to ``sample``
        keys and cached per plan (one device eval per generation).
        Point-only plans have no prediction window: 0."""
        key = ("build_disp", float(q), int(sample))
        cached = self._cache.get(key)
        if cached is not None:
            return cached
        if self.point_only or self.n == 0:
            self._cache[key] = 0.0
            return 0.0
        idx = np.linspace(0, self.n - 1,
                          min(self.n, int(sample))).astype(np.int64)
        lo, hi = self.bounds.predict(self.bounds.state,
                                     self.data[jnp.asarray(idx)])
        lo = np.asarray(lo).astype(np.int64)
        hi = np.asarray(hi).astype(np.int64)
        mid = lo + (hi - lo) // 2
        val = float(np.quantile(np.abs(idx - mid), q))
        self._cache[key] = val
        return val

    def scan(self, q, m: int, backend: str = "jnp",
             interpret: Optional[bool] = None):
        """Convenience: materialize ``m`` records from ``LB(q)``."""
        return self.compile_scan(m, backend, interpret)(q)


def _lb_expr(st: _Statics, backend: str, fused: bool,
             interpret: Optional[bool]) -> Callable:
    """``(q, ops) -> int64 LB ranks`` from a plan's statics alone.

    ``fused`` selects the registered whole-plan kernel over the generic
    bounds -> `lower_bound_windows` path (pallas only; parity tests run
    both).  ``interpret=None`` runs Mosaic kernels on TPU and the Pallas
    interpreter elsewhere."""
    predict = st.predict
    if st.point_only:
        def run_point(q, ops):
            found, pos = predict(ops["state"], q)
            return jnp.where(found, pos, -1).astype(jnp.int64)

        return run_point

    if backend == "pallas":
        if fused:
            lookup = st.fused.lookup

            def run_fused(q, ops):
                return lookup(ops["fused"], ops["data"], q,
                              interpret=interpret,
                              planes=ops.get("planes")).astype(jnp.int64)

            return run_fused

        from repro.kernels.bounded_search.ops import lower_bound_windows

        def run_pallas(q, ops):
            lo, _hi = predict(ops["state"], q)
            # window precondition lo <= LB < lo + max_err holds by the
            # bounds contract (LB <= hi <= lo + max_err - 1)
            return lower_bound_windows(
                ops["data"], q, lo, max_width=st.max_err,
                interpret=interpret,
                planes=ops.get("planes")).astype(jnp.int64)

        return run_pallas

    fn = search.SEARCH_FNS[st.last_mile]

    def run_jnp(q, ops):
        lo, hi = predict(ops["state"], q)
        return fn(ops["data"], q, lo, hi, st.max_err).astype(jnp.int64)

    return run_jnp


# ---------------------------------------------------------------------------
# Lowering
# ---------------------------------------------------------------------------
def lower(build: base.IndexBuild, data_jnp,
          last_mile: Optional[str] = None) -> LookupPlan:
    """Lower a built index to its `LookupPlan`.

    The lowering contract is exactly the `IndexBuild` surface: ``lookup``
    is the pure bounds predictor, ``meta["max_err"]`` the static window
    bound.  ``last_mile`` defaults to the hyperparameter the index was
    built with (falling back to binary) — the policy every consumer
    shared before plans existed.
    """
    if last_mile is None:
        last_mile = build.hyper.get("last_mile", "binary")
    n = int(build.meta.get("n", data_jnp.shape[0]))
    bounds = BoundsStage(
        state=build.state,
        predict=build.lookup,
        max_err=int(build.meta.get("max_err", n + 1)),
    )
    return LookupPlan(
        name=build.name,
        bounds=bounds,
        data=data_jnp,
        n=n,
        last_mile=last_mile,
        point_only=bool(build.meta.get("point_only", False)),
        fused=FUSED_LOWERERS.get(build.name),
        meta=dict(build.hyper),
    )


def _rmi_fused_lookup(state, data, q, interpret=None, planes=None):
    from repro.kernels.rmi_lookup import ops as rops

    return rops.rmi_lookup(state, data, q, interpret=interpret,
                           planes=planes)


@register_fused("rmi", _rmi_fused_lookup)
def _rmi_fused(plan: LookupPlan):
    """Whole-plan executor for RMI: the stage-2 fetch kernel + the
    last-mile kernel (`kernels/rmi_lookup`).  The f32 state is refit from
    the plan's keys with error tables re-verified through the lookup's
    own arithmetic, so the result is still the exact LB rank —
    bit-identical to every other backend."""
    from repro.kernels.rmi_lookup import ops as rops

    return rops.prepare_f32_state(
        np.asarray(plan.data), branching=int(plan.meta.get("branching", 1024)))
