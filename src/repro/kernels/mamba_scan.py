"""mamba_scan — the mamba1 selective-scan recurrence.

  h_t = dA_t * h_{t-1} + dBu_t          (h: [C, N] per step)
  y_t = h_t . C_t                       (contraction over the state dim N)

Grid: (C/bc, S/bs), sequence innermost; the [bc, N] state sits in VMEM
scratch while the per-step dA/dBu blocks stream past it.  N (the SSM state,
16 for falcon-mamba) rides in the lane dimension of the streamed blocks;
each step's C_t . h contraction runs on the MXU as a [1, N] x [N, bc] dot.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(dA_ref, dBu_ref, c_ref, y_ref, h_ref, *, bs: int, rows: int):
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def chunk(c, h):  # h [bc, N]; ``rows`` sequence steps per tile-aligned load
        base = pl.multiple_of(c * rows, rows)
        cm = c_ref[pl.ds(base, rows), :].astype(jnp.float32)  # [rows, N]
        ys = jnp.zeros((rows, h.shape[0]), jnp.float32)
        row_id = jax.lax.broadcasted_iota(jnp.int32, ys.shape, 0)
        for r in range(rows):
            t = base + r
            h = dA_ref[t].astype(jnp.float32) * h + dBu_ref[t].astype(jnp.float32)
            y = jax.lax.dot_general(  # C_t . h^T -> [1, bc], contraction over N
                cm[r : r + 1], h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ys = jnp.where(row_id == r, y, ys)
        y_ref[pl.ds(base, rows), :] = ys.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, bs // rows, chunk, h_ref[...])


def mamba_scan_kernel(dA, dBu, C, *, block_s: int = 16, block_c: int = 256,
                      interpret: bool = True):
    """dA, dBu [S, Ch, N]; C [S, N] -> y [S, Ch].

    N rides in the lanes and is padded to 128 in VMEM, so a [bs, bc, N]
    f32 block costs bs*bc*512 bytes: the default 16 x 256 keeps the four
    double-buffered input blocks at 8 MiB, inside the 16 MiB scoped limit."""
    S, Ch, N = dA.shape
    bs, bc = min(block_s, S), min(block_c, Ch)
    assert S % bs == 0 and Ch % bc == 0
    grid = (Ch // bc, S // bs)
    # rows per load: one sublane tile of the input dtype (8 f32 / 16 bf16)
    rows = math.gcd(bs, 32 // jnp.dtype(dA.dtype).itemsize)
    kernel = functools.partial(_mamba_kernel, bs=bs, rows=rows)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, bc, N), lambda c, s: (s, c, 0)),
            pl.BlockSpec((bs, bc, N), lambda c, s: (s, c, 0)),
            pl.BlockSpec((bs, N), lambda c, s: (s, 0)),
        ],
        out_specs=pl.BlockSpec((bs, bc), lambda c, s: (s, c)),
        out_shape=jax.ShapeDtypeStruct((S, Ch), dA.dtype),
        scratch_shapes=[pltpu.VMEM((bc, N), jnp.float32)],
        interpret=interpret,
        name="mamba_scan",
    )(dA, dBu, C)
