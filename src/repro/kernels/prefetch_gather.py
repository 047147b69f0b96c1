"""prefetch_gather — hint-driven row gather (the CAPre kernel).

The predicted row indices (the *prefetching hints* of the access plan) are
passed as **scalar-prefetch operands** (``pltpu.PrefetchScalarGridSpec``):
the BlockSpec ``index_map`` reads them to decide which HBM row block to DMA
into VMEM for each grid step, so the pipeline fetches the predicted rows
ahead of the compute that consumes them — the exact TPU analogue of the
paper's generated prefetch methods running ahead of the application.

Used for: embedding-row gather, MoE expert-bank staging, KV-page gather.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def _gather_kernel(idx_ref, table_ref, out_ref):
    # the BlockSpec index_map already steered the DMA to row idx[b];
    # the body is a plain VMEM copy.
    del idx_ref
    out_ref[...] = table_ref[...]


def prefetch_gather_kernel(table, idx, *, block_d: int = 512, interpret: bool = True):
    """table [N, D] (D % 128 == 0), idx [B] int32 -> out [B, D].

    Rows travel as [1, block_d] tiles of a [N, 1, D] view, so the block's
    last two dims are whole (1) and lane-aligned (block_d)."""
    N, D = table.shape
    (B,) = idx.shape
    block_d = min(block_d, D)
    assert D % block_d == 0 and block_d % LANE == 0, (D, block_d)
    grid = (B, D // block_d)
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, 1, block_d), lambda b, j, idx_ref: (idx_ref[b], 0, j)),
            ],
            out_specs=pl.BlockSpec((None, 1, block_d), lambda b, j, idx_ref: (b, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, 1, D), table.dtype),
        interpret=interpret,
        name="prefetch_gather",
    )(idx.astype(jnp.int32), table.reshape(N, 1, D))
    return out.reshape(B, D)
