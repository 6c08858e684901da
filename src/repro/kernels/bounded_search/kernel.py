"""Batched lower-bound search inside a known window — the last-mile hot path.

The paper's last-mile search is a dependent-load chain per query (binary
search inside the bound).  The TPU-native form (DESIGN.md §2) replaces the
chain with one vectorized rank count: stream the DATA TILE that holds the
query's window start (and the tile after it, since a window may spill
over the tile edge) HBM->VMEM, and resolve the query as
``pos = lo + #{i in [lo, n) of the two tiles : D[i] < q}``.  Counting past
``LB(q)`` adds nothing because the keys are sorted, so the count is exact
whenever ``LB(q)`` lies in the two tiles — guaranteed when the window
``[lo, lo + max_width)`` is no wider than one tile.

Grid: one step per query.  The scalar-prefetched tile index of each query
drives two BlockSpecs over the same data planes (tile t and tile t+1);
consecutive queries of the same tile re-use the resident blocks.  The
grid therefore grows with the batch, not with the key count.  The compare
is a masked compare-and-count over two (16, 128) blocks: no in-VMEM
gather, every block aligned to the (8, 128) vector tiling.

Keys are int32 planes with the sign bit flipped (kernels/common.py), so a
signed compare orders them as the unsigned 64-bit keys.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, block_sum, less_u64

DATA_TILE = 2048                 # keys per VMEM tile: (16, 128) per plane
TILE_ROWS = DATA_TILE // LANES
_ZERO = np.int32(0)               # block indices stay int32 under x64


def _kernel(tile_ref, qhi_ref, qlo_ref, lo_ref,
            dhi0_ref, dlo0_ref, dhi1_ref, dlo1_ref, out_ref, *, n: int):
    i = pl.program_id(0)
    t, qhi, qlo, lo = tile_ref[i], qhi_ref[i], qlo_ref[i], lo_ref[i]
    offs = (jax.lax.broadcasted_iota(jnp.int32, (TILE_ROWS, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (TILE_ROWS, LANES), 1))

    def count(dhi_ref, dlo_ref, base):
        idx = base + offs
        less = less_u64(dhi_ref[...], dlo_ref[...], qhi, qlo)
        return block_sum(less & (idx >= lo) & (idx < n))

    # tile t+1 is clamped to the last tile at the end of the array; its
    # positions are then >= n and masked out
    out_ref[i] = (lo + count(dhi0_ref, dlo0_ref, t * DATA_TILE)
                  + count(dhi1_ref, dlo1_ref, (t + 1) * DATA_TILE))


def lower_bound_kernel(
    tile,                # [m] int32 data tile holding each window start
    qhi, qlo,            # [m] int32 query planes
    lo,                  # [m] int32 window starts, in [0, n)
    dhi, dlo,            # [n_pad // 128, 128] int32 data planes
    *, n: int, interpret: bool = False,
):
    """Exact ``LB(q)`` for each query whose LB lies in tiles t, t+1."""
    m = tile.shape[0]
    last = dhi.shape[0] // TILE_ROWS - 1
    tile0 = pl.BlockSpec((TILE_ROWS, LANES), lambda i, t, *_: (t[i], _ZERO))
    tile1 = pl.BlockSpec((TILE_ROWS, LANES),
                         lambda i, t, *_: (jnp.minimum(t[i] + 1, last), _ZERO))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(m,),
        in_specs=[tile0, tile0, tile1, tile1],
        out_specs=pl.BlockSpec((m,), lambda i, *_: (_ZERO,),
                               memory_space=pltpu.SMEM),
    )
    return pl.pallas_call(
        functools.partial(_kernel, n=n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m,), jnp.int32),
        name="bounded_search",
        interpret=interpret,
    )(tile, qhi, qlo, lo, dhi, dlo, dhi, dlo)
