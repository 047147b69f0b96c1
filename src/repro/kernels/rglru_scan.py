"""rglru_scan — the RG-LRU recurrence h_t = a_t * h_t-1 + g_t.

Grid: (M/bm, S/bs) with the sequence dimension innermost: for each channel
block the state lives in VMEM scratch while sequence blocks stream past it.
Inputs are the precomputed per-step decay ``a`` and gated input ``g``
(elementwise products are fused upstream); channels are the 128-lane axis.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, g_ref, y_ref, h_ref, *, bs: int, rows: int):
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def chunk(c, h):  # h [1, bm]; ``rows`` sequence steps per tile-aligned load
        base = pl.multiple_of(c * rows, rows)
        a = a_ref[pl.ds(base, rows), :].astype(jnp.float32)  # [rows, bm]
        g = g_ref[pl.ds(base, rows), :].astype(jnp.float32)
        row_id = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        ys = jnp.zeros_like(a)
        for r in range(rows):
            h = a[r : r + 1] * h + g[r : r + 1]
            ys = jnp.where(row_id == r, h, ys)
        y_ref[pl.ds(base, rows), :] = ys.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, bs // rows, chunk, h_ref[...])


def rglru_scan_kernel(a, g, *, block_s: int = 256, block_m: int = 512,
                      interpret: bool = True):
    """a, g [S, M] -> y [S, M] (h_0 = 0)."""
    S, M = a.shape
    bs, bm = min(block_s, S), min(block_m, M)
    assert S % bs == 0 and M % bm == 0
    grid = (M // bm, S // bs)  # sequence innermost (sequential)
    # rows per load: one sublane tile of the input dtype (8 f32 / 16 bf16)
    rows = math.gcd(bs, 32 // jnp.dtype(a.dtype).itemsize)
    kernel = functools.partial(_rglru_kernel, bs=bs, rows=rows)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, bm), lambda m, s: (s, m)),
            pl.BlockSpec((bs, bm), lambda m, s: (s, m)),
        ],
        out_specs=pl.BlockSpec((bs, bm), lambda m, s: (s, m)),
        out_shape=jax.ShapeDtypeStruct((S, M), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bm), jnp.float32)],
        interpret=interpret,
        name="rglru_scan",
    )(a, g)
