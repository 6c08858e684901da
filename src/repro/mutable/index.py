"""Mutable learned index: base generation + delta, merged by rank sum.

The merged lookup is one jitted program per base generation:

    LB_merged(q) = LB_base(q) + LB_delta(q)

`LB_base` is the generation's `LookupPlan` (predict + bounded last-mile,
`repro.core.plan`) inlined through the plan's `compile_merged` transform
— which means the mutable read path runs on whatever backend the
generation serves with (jnp or Pallas kernels) for free; `LB_delta` is a
vectorized `searchsorted` over the padded device delta.  Base and delta
are disjoint sorted sets, so the two lower bounds add exactly — every
position the read path returns is identical to a lookup over the fully
merged sorted array (the invariant `tests/test_workloads_mutable.py`
pins against `oracle_replay` for every LB-capable index type x dataset).

Construction is declarative (DESIGN.md §12): the index is addressed by
an `IndexSpec` (pass one directly, or the legacy index/hyper/backend
arguments are folded into one), every build runs through `spec.build`,
and an optional `Tuner` makes compaction ADAPTIVE — each fold re-runs
the budget search against the delta-merged key set, so the spec (and
backend) can change when the data distribution does (the ROADMAP's
delta-aware retuning item).

Concurrency model (DESIGN.md §10.3): the only mutable cell is one
`MutableView` pointer.  Inserts and compaction-publish replace it under
a mutation lock; readers grab the current view with one lock-free-ish
read and keep a fully consistent (generation, delta) PAIR for the whole
batch — swapping either half atomically with the other is exactly what
prevents double counting when a compaction folds delta keys into a new
base.  Compaction itself (merge + rebuild, plus the optional retune)
runs outside every lock and publishes through
`IndexRegistry.build_and_publish` / `publish`, the serving registry's
atomic hot-swap.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, Optional

import numpy as np

from repro.core import spec as spec_mod
from repro.mutable.delta import PAD_QUANTUM, DeltaBuffer
from repro.serve.lookup.registry import (DEFAULT_NAME, Generation,
                                         IndexRegistry)

__all__ = ["LB_INDEXES", "MutableIndex", "MutableView", "make_merged_fn"]

#: Index types with lower-bound semantics — the ones a delta can merge
#: with by rank correction.  `robin_hash` is point-only (no LB, paper
#: §4.1.1) and stays read-only.
LB_INDEXES = ("rmi", "pgm", "radix_spline", "btree", "ibtree", "rbs",
              "binary_search")


def make_merged_fn(plan, backend: str = "jnp") -> Callable:
    """jit'd merged lookup: (queries, padded delta) -> merged positions.

    A thin name over the plan's delta rank-correction transform
    (`LookupPlan.compile_merged`): the base LB expression is inlined for
    the chosen backend and the delta is an ARGUMENT, not a closure
    constant — the compile cache keys on (query bucket, delta bucket)
    shapes only, so insert traffic re-uses the compiled program until
    the delta crosses a pow-2 pad boundary."""
    return plan.compile_merged(backend=backend)


@dataclasses.dataclass(frozen=True)
class MutableView:
    """One immutable (generation, delta) snapshot — the unit readers pin."""

    generation: Generation
    base_np: np.ndarray        # host copy of the generation's sorted keys
    delta: DeltaBuffer
    merged_fn: Callable        # shared per generation across delta updates

    def lookup(self, q):
        """Device merged lookup; `q` is a jnp/np uint64 batch."""
        return self.merged_fn(q, self.delta.device)

    @property
    def n_keys(self) -> int:
        """Logical key count of the merged view."""
        return int(self.base_np.size) + self.delta.count


class MutableIndex:
    """Delta-buffered writes + merged reads over one registry name."""

    def __init__(self, keys: np.ndarray, index: str = "rmi",
                 hyper: Optional[Dict[str, Any]] = None,
                 last_mile: Optional[str] = None,
                 backend: str = "jnp",
                 compact_threshold: int = 4096,
                 registry: Optional[IndexRegistry] = None,
                 name: str = DEFAULT_NAME,
                 pad_quantum: int = PAD_QUANTUM,
                 spec: Optional[spec_mod.IndexSpec] = None,
                 tuner: Optional[spec_mod.Tuner] = None):
        if compact_threshold < 1:
            raise ValueError("compact_threshold must be >= 1")
        if spec is not None:
            self.spec = spec_mod.coerce(spec, hyper)   # spec wins wholesale
        else:
            self.spec = spec_mod.coerce(index, hyper, backend=backend,
                                        last_mile=last_mile)
        self.tuner = tuner
        self.compact_threshold = int(compact_threshold)
        self.registry = registry if registry is not None else IndexRegistry()
        self.name = name
        self.pad_quantum = int(pad_quantum)
        self._mu = threading.Lock()          # view-pointer mutations
        self._compact_mu = threading.Lock()  # one compaction at a time
        self._view: Optional[MutableView] = None
        self.reset(keys)

    # -- spec-derived views (kept in sync across retunes) -----------------
    @property
    def index(self) -> str:
        return self.spec.index

    @property
    def hyper(self) -> Dict[str, Any]:
        return dict(self.spec.hyper)

    @property
    def last_mile(self) -> Optional[str]:
        return self.spec.last_mile

    @property
    def backend(self) -> str:
        return self.spec.backend

    # -- lifecycle -------------------------------------------------------
    def _publish_base(self, keys: np.ndarray) -> MutableView:
        keys = np.asarray(keys, dtype=np.uint64)
        gen = self.registry.build_and_publish(self.spec, keys,
                                              name=self.name)
        return MutableView(generation=gen, base_np=keys,
                           delta=DeltaBuffer.empty(self.pad_quantum),
                           merged_fn=make_merged_fn(gen.plan, self.backend))

    def reset(self, keys: np.ndarray) -> MutableView:
        """Replace the whole key set: fresh base, empty delta."""
        view = self._publish_base(keys)
        with self._mu:
            self._view = view
        return view

    # -- read side -------------------------------------------------------
    def view(self) -> MutableView:
        with self._mu:
            return self._view

    def lookup(self, q) -> np.ndarray:
        """Host convenience: merged LB positions as int64 numpy."""
        import jax.numpy as jnp

        q = jnp.asarray(np.asarray(q, dtype=np.uint64))
        return np.asarray(self.view().lookup(q), dtype=np.int64)

    # -- write side ------------------------------------------------------
    def insert(self, keys) -> np.ndarray:
        """Admit keys into the delta (set semantics); returns the 0/1
        admitted flag per input key."""
        with self._mu:
            view = self._view
            delta, admitted = view.delta.with_inserted(view.base_np, keys)
            if delta is not view.delta:
                self._view = dataclasses.replace(view, delta=delta)
        return admitted

    @property
    def delta_count(self) -> int:
        return self.view().delta.count

    @property
    def needs_compaction(self) -> bool:
        return self.delta_count >= self.compact_threshold

    # -- autotune apply --------------------------------------------------
    def republish(self, spec, build=None) -> Optional[Generation]:
        """Hot-swap the base generation to a new spec WITHOUT folding
        the delta — the autotune retuner's apply path (DESIGN.md §17).

        The base key set is unchanged, so the caller's oracle-verified
        build for it can be published as-is; the delta is carried over
        verbatim, so inserts admitted at any point survive, and reads
        stay consistent because the mutable read path pins (generation,
        delta) PAIRS — the swap is one view-pointer assignment like
        compaction's.  Returns None if a reset/compaction replaced the
        base mid-flight (the verified build no longer matches the
        serving base — the caller must re-tune, not force the swap).
        """
        with self._compact_mu:
            snap = self.view()
            new_spec = spec_mod.coerce(spec)
            b = build if build is not None \
                else spec_mod.build(new_spec, snap.base_np)
            b.meta["spec"] = new_spec
            with self._mu:
                if self._view.generation is not snap.generation:
                    return None
                gen = self.registry.publish(b, snap.generation.data,
                                            name=self.name,
                                            last_mile=new_spec.last_mile,
                                            backend=new_spec.backend,
                                            spec=new_spec,
                                            keys=snap.base_np)
                self.spec = new_spec
                self._view = MutableView(
                    generation=gen, base_np=snap.base_np,
                    delta=self._view.delta,
                    merged_fn=make_merged_fn(gen.plan, new_spec.backend))
            return gen

    # -- compaction ------------------------------------------------------
    def compact(self) -> Optional[Generation]:
        """Fold the current delta into a fresh base generation.

        Snapshot -> merge -> (retune) -> rebuild -> hot-swap publish.
        With a `Tuner` configured, the rebuild's spec is CHOSEN against
        the delta-merged key set (DESIGN.md §12.4) — the budget search
        runs where the rebuild cost is already being paid, so a drifted
        key distribution gets a freshly-tuned spec+backend and the
        chosen build is published as-is (tuned builds are bit-identical
        to direct builds of the same spec, so results cannot move).

        The rebuild (seconds of host numpy) runs outside every lock;
        the publish + pointer swap hold the mutation lock and are
        cheap, so inserts admitted DURING the rebuild are preserved:
        the new view keeps exactly the keys the snapshot did not cover.
        If a `reset` replaced the whole key set mid-rebuild, the
        snapshot's generation is no longer current and the rebuild is
        DISCARDED — publishing it would resurrect the discarded key
        set.  Returns the new generation, or None if the delta was
        empty or the rebuild was abandoned.
        """
        with self._compact_mu:
            snap = self.view()
            if snap.delta.count == 0:
                return None
            merged_keys = np.concatenate([snap.base_np, snap.delta.keys_np])
            merged_keys.sort(kind="stable")
            if self.tuner is not None:
                result = self.tuner.tune(merged_keys)
                new_spec, build = result.spec, result.build
                # the tuner decides what it was ASKED to decide: with a
                # single candidate backend it performed no backend
                # selection, so the index's serving backend survives the
                # retune; an unset last-mile likewise stays configured
                if len(self.tuner.backends) == 1:
                    new_spec = new_spec.replace(backend=self.spec.backend)
                if new_spec.last_mile is None and \
                        self.spec.last_mile is not None:
                    new_spec = new_spec.replace(
                        last_mile=self.spec.last_mile)
                build.meta["spec"] = new_spec
            else:
                new_spec = self.spec
                build = spec_mod.build(new_spec, merged_keys)
            data = self.registry.place_keys(merged_keys)
            with self._mu:
                if self._view.generation is not snap.generation:
                    return None   # reset() raced the rebuild: stale, drop it
                gen = self.registry.publish(build, data, name=self.name,
                                            last_mile=new_spec.last_mile,
                                            backend=new_spec.backend,
                                            spec=new_spec, keys=merged_keys)
                self.spec = new_spec
                leftover = self._view.delta.minus(snap.delta)
                self._view = MutableView(
                    generation=gen, base_np=merged_keys, delta=leftover,
                    merged_fn=make_merged_fn(gen.plan, new_spec.backend))
            return gen
