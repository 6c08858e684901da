"""Shared helpers for the TPU lookup kernels.

TPU VPU/MXU have no native 64-bit integer or float64 path, so 64-bit keys
are carried as two uint32 planes (hi, lo) and compared lexicographically —
the hardware adaptation of the paper's 64-bit-key experiments (DESIGN.md §2).
32-bit datasets (paper §4.2.2) use a zero hi plane, one uniform code path.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

#: Vector lane width: the minor dimension of every kernel block.
LANES = 128


def block_sum(x):
    """In-kernel int32 sum of a 2-D block, as a scalar.  Reduces to a
    one-element vector and extracts it: Mosaic lowers a reduction
    straight to a scalar through ``jnp.sum``, which promotes int32 to
    int64 (unsupported in a kernel) when x64 mode is on."""
    return jnp.squeeze(jnp.sum(x[None].astype(jnp.int32), axis=(1, 2),
                               dtype=jnp.int32))


def lb_steps(max_width: int) -> int:
    """Fixed trip count covering any bounded window of width <= max_width."""
    return int(np.ceil(np.log2(max(2, int(max_width) + 1)))) + 1


def branchless_lower_bound(data, q, lo, hi, max_width: int,
                           side: str = "left", index_dtype=None):
    """Branchless lower/upper bound in ``[lo, hi]`` (hi INCLUSIVE).

    The ONE bounded binary search in the repo, parameterized by position
    dtype: `repro.core.search.bounded_binary` runs it in int64 (x64 core
    path), the Pallas overflow fallback in `kernels.bounded_search.ops`
    in int32 (kernel wrappers never require x64 mode).  ``max_width`` is
    a static bound on ``hi - lo + 1``; it fixes the trip count so the
    loop lowers to a fixed-depth HLO with no data-dependent control
    flow.  Position ``n`` (one past the end) compares as +infinity.
    """
    n = data.shape[0]
    if index_dtype is None:
        index_dtype = lo.dtype
    lo = lo.astype(index_dtype)
    count = (hi + 1 - lo).astype(index_dtype)
    count = jnp.maximum(count, 0)

    def body(_, carry):
        lo, count = carry
        step = count // 2
        idx = lo + step
        probe = jnp.take(data, jnp.clip(idx, 0, n - 1), mode="clip")
        if side == "left":
            go_right = probe < q
        else:  # upper_bound: first element > q
            go_right = probe <= q
        go_right &= idx < n
        lo = jnp.where(go_right, lo + step + 1, lo)
        count = jnp.where(go_right, count - step - 1, step)
        return lo, count

    lo, _ = jax.lax.fori_loop(0, lb_steps(max_width), body, (lo, count))
    return lo


def split_u64(a):
    """Key array -> (hi, lo) uint32 planes (numpy or jnp).

    32-bit-or-narrower inputs (paper §4.2.2; int32 serving tables) get a
    zero hi plane without ever touching 64-bit ops — usable in contexts
    where jax x64 is disabled."""
    if isinstance(a, np.ndarray):
        if a.dtype.itemsize <= 4:
            lo = a.astype(np.uint32)
            return np.zeros_like(lo), lo
        a = a.astype(np.uint64)
        return (a >> np.uint64(32)).astype(np.uint32), a.astype(np.uint32)
    if jnp.dtype(a.dtype).itemsize <= 4:
        lo = a.astype(jnp.uint32)
        return jnp.zeros_like(lo), lo
    a = a.astype(jnp.uint64)
    return (a >> jnp.uint64(32)).astype(jnp.uint32), a.astype(jnp.uint32)


def key_planes(a):
    """Key array -> (hi, lo) int32 planes with the sign bit flipped.

    Flipping the sign bit maps unsigned order onto signed order, so the
    kernels compare keys with plain int32 compares (`less_u64`)."""
    hi, lo = split_u64(a)
    flip = jnp.uint32(0x80000000)
    return (jax.lax.bitcast_convert_type(hi ^ flip, jnp.int32),
            jax.lax.bitcast_convert_type(lo ^ flip, jnp.int32))


def data_planes(data, tile: int):
    """Sorted keys -> (hi, lo) `key_planes` padded to a multiple of
    ``tile`` and shaped ``[n_pad // LANES, LANES]`` — the layout the
    bounded-search kernel streams.  Padding holds the largest key; the
    kernel masks it out by position anyway."""
    n = data.shape[0]
    pad = pad_to(max(n, 1), tile) - n
    out = []
    for p in key_planes(data):
        p = jnp.pad(p, (0, pad), constant_values=np.int32(0x7FFFFFFF))
        out.append(p.reshape(-1, LANES))
    return tuple(out)


def merge_u64(hi, lo):
    return (np.asarray(hi).astype(np.uint64) << np.uint64(32)) | np.asarray(
        lo
    ).astype(np.uint64)


def less_u64(a_hi, a_lo, b_hi, b_lo):
    """(a < b) for keys as (hi, lo) planes — uint32 planes, or the
    sign-flipped int32 `key_planes`; works on jnp values in-kernel."""
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo))


def pad_pow2(x: int, minimum: int = 128) -> int:
    n = minimum
    while n < x:
        n *= 2
    return n


def pad_to(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple
