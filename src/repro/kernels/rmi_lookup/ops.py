"""jit'd wrappers: f32 RMI state preparation + fused lookup pipeline.

``prepare_f32_state`` re-verifies the stage-2 error table through the exact
f32 arithmetic the lookup runs (the shared ``_stage2`` expression over the
kernel-fetched coefficients), so the bounds stay valid even though TPU
model math is float32 while the paper's reference implementations use
float64.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.common import LANES, pad_pow2, pad_to
from repro.kernels.rmi_lookup import ref as _ref
from repro.kernels.rmi_lookup.kernel import TABLE_BLOCK, rmi_infer_kernel
from repro.kernels.bounded_search.ops import (QUERY_CHUNK,
                                              lower_bound_windows)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class F32RMIState:
    c0: Any
    c1: Any
    x0: Any
    inv_range: Any
    a2: Any
    b2: Any
    err: Any
    tables: Any      # (a2, b2, err) as [T_pad // 128, 128] int32 kernel blocks
    scale: float
    branching: int
    n: int
    max_err: int

    def tree_flatten(self):
        leaves = (self.c0, self.c1, self.x0, self.inv_range,
                  self.a2, self.b2, self.err, self.tables)
        aux = (self.scale, self.branching, self.n, self.max_err)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)


@jax.jit
def _infer_u_bkt(state: "F32RMIState", q):
    """Stage-1 inference: the ONE code path used by both the error-table
    verification (on keys) and rmi_bounds (on queries).  Build/lookup must
    share the compiled arithmetic bit-for-bit (XLA may contract a*u+b into
    an FMA; a numpy replica can differ by 1 ulp and misassign boundary
    keys' errors — see repro.core.rmi)."""
    u = _ref.f32_u(state, q)
    p1 = state.c0 * u + state.c1
    bkt = jnp.clip(
        jnp.floor(p1 * jnp.float32(state.scale)), 0, state.branching - 1
    ).astype(jnp.int32)
    return u, bkt


def _stage2(a, b, u):
    """Stage-2 prediction: the ONE expression both the error-table
    verification and the lookup evaluate."""
    return a * u + b


def _kernel_tables(a2f, b2f, err_i):
    """Stage-2 table -> the kernel's int32 blocks (f32 as bit patterns)."""
    pad = pad_to(len(err_i), TABLE_BLOCK) - len(err_i)

    def block(x):
        return jnp.asarray(np.pad(x.view(np.int32), (0, pad)).reshape(-1, LANES))

    return (block(a2f), block(b2f), block(err_i))


def prepare_f32_state(keys: np.ndarray, branching: int = 4096,
                      data=None) -> F32RMIState:
    """Fit a linear/linear RMI (f64 numpy fit, as repro.core.rmi) and verify
    its error table under the kernel's f32 inference pipeline.

    ``keys`` is the host copy of the sorted keys; ``data``, where the
    caller already holds the same uint64 keys on the device, is what
    stage-1 inference runs on instead of a fresh upload (same values, so
    the state is the same)."""
    keys = np.asarray(keys).astype(np.uint64)
    n = len(keys)
    B = int(branching)
    x = keys.astype(np.float64)
    y = np.arange(n, dtype=np.float64)

    x0 = np.float32(x[0])
    rng = np.float32(x[-1]) - x0
    inv_range = np.float32(1.0 / rng) if rng > 0 else np.float32(1.0)
    scale = B / n

    # stage-1 fit (f64 for conditioning, stored f32)
    u64 = (x - float(x0)) * float(inv_range)
    su, sy = u64.sum(), y.sum()
    suu, suy = (u64 * u64).sum(), (u64 * y).sum()
    denom = n * suu - su * su
    a1 = max((n * suy - su * sy) / denom, 0.0) if denom > 0 else 0.0
    b1 = (sy - a1 * su) / n

    state = F32RMIState(
        c0=jnp.float32(a1), c1=jnp.float32(b1), x0=jnp.float32(x0),
        inv_range=jnp.float32(inv_range),
        a2=jnp.zeros(1, jnp.float32), b2=jnp.zeros(1, jnp.float32),
        err=jnp.zeros(1, jnp.int32), tables=(),
        scale=scale, branching=B, n=n, max_err=1,
    )
    # bucket assignment + u through the EXACT kernel-side math
    if data is None or data.dtype != jnp.uint64:
        data = jnp.asarray(keys)
    u_j, bkt_j = _infer_u_bkt(state, data)
    u32 = np.asarray(u_j, np.float64)
    bkt = np.asarray(bkt_j).astype(np.int64)
    bkt = np.maximum.accumulate(bkt)  # no-op safeguard (inference monotone)

    # stage-2 grouped least squares (f64 fit on the f32-rounded u)
    cnt = np.bincount(bkt, minlength=B).astype(np.float64)
    su2 = np.bincount(bkt, weights=u32, minlength=B)
    sy2 = np.bincount(bkt, weights=y, minlength=B)
    suu2 = np.bincount(bkt, weights=u32 * u32, minlength=B)
    suy2 = np.bincount(bkt, weights=u32 * y, minlength=B)
    den2 = cnt * suu2 - su2 * su2
    ok = den2 > 1e-30
    a2 = np.where(ok, (cnt * suy2 - su2 * sy2) / np.where(ok, den2, 1.0), 0.0)
    a2 = np.maximum(a2, 0.0)
    b2 = np.where(cnt > 0, (sy2 - a2 * su2) / np.where(cnt > 0, cnt, 1.0), 0.0)
    first_pos = np.searchsorted(bkt, np.arange(B), side="left").astype(np.float64)
    empty = cnt == 0
    b2 = np.where(empty, first_pos, b2)

    a2f = a2.astype(np.float32)
    b2f = b2.astype(np.float32)

    # error verification through f32 arithmetic (same expression as kernel)
    pred = np.asarray(
        jax.jit(lambda a, b, u, k: _stage2(jnp.take(a, k), jnp.take(b, k), u))(
            jnp.asarray(a2f), jnp.asarray(b2f), u_j, jnp.asarray(bkt, jnp.int32)
        ),
        np.float64,
    )
    err = np.zeros(B, np.float64)
    np.maximum.at(err, bkt, np.abs(pred - y))
    # both-side boundary augmentation (see repro.core.rmi)
    nonempty = np.flatnonzero(~empty)
    fp = first_pos[nonempty].astype(np.int64)

    def _eval(bids, kidx):
        return np.asarray(
            jax.jit(lambda a, b, u, k: _stage2(jnp.take(a, k), jnp.take(b, k), u))(
                jnp.asarray(a2f), jnp.asarray(b2f),
                u_j[jnp.asarray(kidx)], jnp.asarray(bids, jnp.int32)
            ),
            np.float64,
        )

    hp = fp > 0
    np.maximum.at(err, nonempty[hp],
                  np.abs(_eval(nonempty[hp], fp[hp] - 1) - fp[hp].astype(np.float64)))
    lp = np.searchsorted(bkt, nonempty, side="right") - 1
    hn = lp < n - 1
    np.maximum.at(err, nonempty[hn],
                  np.abs(_eval(nonempty[hn], lp[hn] + 1) - (lp[hn] + 1.0)))
    # empty buckets: exact LB is first_pos; only f32 rounding of b2 matters
    err[empty] = np.abs(b2f[empty].astype(np.float64) - first_pos[empty])

    err_i = (np.ceil(err) + 1).astype(np.int32)
    state = dataclasses.replace(
        state,
        a2=jnp.asarray(a2f), b2=jnp.asarray(b2f), err=jnp.asarray(err_i),
        tables=_kernel_tables(a2f, b2f, err_i),
        max_err=int(2 * err_i.max() + 2),
    )
    return state


@functools.partial(jax.jit, static_argnames=("interpret",))
def rmi_bounds(state: F32RMIState, queries, interpret: Optional[bool] = None):
    """Stage-2 models fetched by the Pallas kernel: queries -> (lo, hi)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m = queries.shape[0]
    u, bkt = _infer_u_bkt(state, queries)
    parts = [rmi_infer_kernel(bkt[s:s + QUERY_CHUNK], *state.tables,
                              interpret=interpret)
             for s in range(0, m, QUERY_CHUNK)]
    a_bits, b_bits, err = (p[0] if len(parts) == 1 else jnp.concatenate(p)
                           for p in zip(*parts))
    pred = _stage2(jax.lax.bitcast_convert_type(a_bits, jnp.float32),
                   jax.lax.bitcast_convert_type(b_bits, jnp.float32), u)
    pred = jnp.clip(pred, -1.0, float(state.n) + 1.0)  # guard int32 overflow
    lo = jnp.clip(jnp.floor(pred).astype(jnp.int32) - err, 0, state.n)
    hi = jnp.clip(jnp.ceil(pred).astype(jnp.int32) + err, 0, state.n)
    return lo, hi


def window_width(state: F32RMIState) -> int:
    """The static window the lookup searches: ``max_err`` rounded up to
    the power of two the kernel pads it to anyway.  A wider bound is
    still a valid one, so answers are unchanged, and states whose bounds
    share a power of two share one program."""
    return pad_pow2(int(state.max_err), minimum=128)


def rmi_lookup(state: F32RMIState, data, queries,
               interpret: Optional[bool] = None, planes=None):
    """End-to-end: kernel-fetched RMI bounds -> last-mile search -> exact LB.
    The stages are named for the profiler as the plan's are: stage-1
    inference and the stage-2 fetch ``predict``, the window search
    ``last_mile``."""
    with jax.named_scope("predict"):
        lo, _hi = rmi_bounds(state, queries, interpret=interpret)
    with jax.named_scope("last_mile"):
        return lower_bound_windows(data, queries, lo,
                                   max_width=window_width(state),
                                   interpret=interpret, planes=planes)
