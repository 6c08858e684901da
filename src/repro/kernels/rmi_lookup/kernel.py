"""RMI stage-2 model fetch: one table row per query, straight from HBM.

The paper's RMI hot path is: stage-1 predict -> select stage-2 model ->
stage-2 predict -> error bound.  The stage-2 table (up to millions of rows)
cannot live in VMEM, and a per-query HBM gather is the slowest thing a TPU
can do.  TPU-native form (DESIGN.md §2): the stage-1 bucket of each query
is scalar-prefetched and drives the BlockSpecs, so each grid step streams
exactly the (8, 128) block of the table that holds the query's row — one
small DMA per query, re-used by consecutive queries of the same block.
The row is picked out of the block by a masked sum over its bit patterns
(exactly one lane is selected, so the sum is the row itself): no in-VMEM
gather.

Model coefficients travel as int32 bit patterns, so the fetch is exact;
the f32 model math runs outside the kernel through the same expression the
error table was verified with (ops.prepare_f32_state) — the beyond-paper
fix for the paper's §4.2.2 observation that 32-bit math "caused floating
point errors".
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, block_sum

BLOCK_ROWS = 8                       # one (8, 128) vector tile per fetch
TABLE_BLOCK = BLOCK_ROWS * LANES     # table rows per fetched block


def _kernel(bkt_ref, a_ref, b_ref, e_ref, a_out, b_out, e_out):
    i = pl.program_id(0)
    # int32 operands throughout: a Python int would trace as int64 in
    # x64 mode, which Mosaic refuses
    k = jax.lax.rem(bkt_ref[i], np.int32(TABLE_BLOCK))   # buckets are >= 0
    flat = (jax.lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, LANES), 1))
    sel = flat == k
    for src, dst in ((a_ref, a_out), (b_ref, b_out), (e_ref, e_out)):
        dst[i] = block_sum(jnp.where(sel, src[...], np.int32(0)))


def rmi_infer_kernel(
    bkt,                     # [m] int32 stage-2 bucket per query
    a2, b2, err,             # [T_pad // 128, 128] int32 table (a2/b2 as f32 bits)
    *, interpret: bool = False,
):
    """``(a2[bkt], b2[bkt], err[bkt])`` per query, as int32 bit patterns."""
    m = bkt.shape[0]
    row = pl.BlockSpec((BLOCK_ROWS, LANES),
                       lambda i, b: (jax.lax.div(b[i], np.int32(TABLE_BLOCK)),
                                     np.int32(0)))
    smem = pl.BlockSpec((m,), lambda i, b: (np.int32(0),),
                        memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m,),
        in_specs=[row, row, row],
        out_specs=[smem, smem, smem],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((m,), jnp.int32)] * 3,
        name="rmi_stage2_fetch",
        interpret=interpret,
    )(bkt, a2, b2, err)
