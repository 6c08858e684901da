"""jit'd wrapper: split keys into planes, run the kernel per query chunk.

Windows wider than one data tile cannot be resolved from two resident
tiles; those plans take the pure-jnp bounded binary search (the shared
dtype-parameterized implementation in `repro.kernels.common`, run in
int32 here), so the result stays exact for every input.  The choice is
static per plan (`kernel_serves`), so a caller can tell which path served
its queries.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import (branchless_lower_bound, data_planes,
                                  key_planes, pad_pow2)
from repro.kernels.bounded_search.kernel import DATA_TILE, lower_bound_kernel

#: Queries per kernel launch: bounds the scalar-memory footprint of the
#: per-query prefetch operands and the result.
QUERY_CHUNK = 16384


def kernel_serves(max_width: int) -> bool:
    """Whether windows of ``max_width`` go to the kernel (True) or to
    the exact jnp fallback (False)."""
    return pad_pow2(max_width, minimum=128) <= DATA_TILE


def kernel_planes(data):
    """The data planes `lower_bound_windows` streams — computed once per
    key array by callers that serve many batches against it."""
    return data_planes(data, DATA_TILE)


@functools.partial(jax.jit, static_argnames=("max_width", "interpret"))
def lower_bound_windows(
    data,                 # [n] sorted uint64 (or uint32) keys
    queries,              # [m] lookup keys
    lo,                   # [m] int32/int64 window starts, LB in [lo, lo+max_width)
    max_width: int,
    interpret: Optional[bool] = None,
    planes=None,          # kernel_planes(data), when the caller keeps them
):
    """Exact LB(q) for every query; window precondition lo <= LB < lo+max_width.

    ``interpret=None`` runs the Mosaic kernel on TPU and the Pallas
    interpreter elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = data.shape[0]
    m = queries.shape[0]
    lo32 = jnp.clip(lo.astype(jnp.int32), 0, max(n - 1, 0))
    if not kernel_serves(max_width) or m == 0:
        hi = jnp.minimum(lo32 + max_width, n) - 1
        return branchless_lower_bound(data, queries, lo32, hi, max_width,
                                      index_dtype=jnp.int32)
    dhi, dlo = kernel_planes(data) if planes is None else planes
    qhi, qlo = key_planes(queries)
    tile = lo32 // DATA_TILE
    outs = [lower_bound_kernel(tile[s:s + QUERY_CHUNK],
                               qhi[s:s + QUERY_CHUNK], qlo[s:s + QUERY_CHUNK],
                               lo32[s:s + QUERY_CHUNK], dhi, dlo,
                               n=n, interpret=interpret)
            for s in range(0, m, QUERY_CHUNK)]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)
