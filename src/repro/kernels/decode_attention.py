"""decode_attention — flash-decode: one query token against a long KV cache.

Grid: (batch*kv_heads, S/bk).  Each program holds the G query heads that
share one KV head, so every KV block is fetched once per group.  The KV
cache streams block-by-block through VMEM while running max/sum/accumulator
scratch carries the online softmax; ``kv_len`` arrives as a scalar-prefetch
operand and blocks entirely past it are skipped (``pl.when``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   bk: int, n_kv: int):
    j = pl.program_id(1)
    kv_len = len_ref[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bk < kv_len)  # skip blocks entirely past the valid length
    def _compute():
        q = q_ref[...].astype(jnp.float32)  # [G, D]
        k = k_ref[...].astype(jnp.float32)  # [bk, D] (may arrive quantized)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (1.0 / (q.shape[-1] ** 0.5))  # [G, bk]
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]  # [G, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_kernel(q, k, v, kv_len, *, block_k: int = 512,
                            interpret: bool = True):
    """q [BKV, G, D] (the G query heads of each KV head); k, v [BKV, S, D];
    kv_len scalar int32 -> [BKV, G, D]."""
    BKV, G, D = q.shape
    S = k.shape[1]
    bk = min(block_k, S)
    assert S % bk == 0
    n_kv = S // bk
    kernel = functools.partial(_decode_kernel, bk=bk, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BKV, n_kv),
            in_specs=[
                pl.BlockSpec((None, G, D), lambda g, j, len_ref: (g, 0, 0)),
                pl.BlockSpec((None, bk, D), lambda g, j, len_ref: (g, j, 0)),
                pl.BlockSpec((None, bk, D), lambda g, j, len_ref: (g, j, 0)),
            ],
            out_specs=pl.BlockSpec((None, G, D), lambda g, j, len_ref: (g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((BKV, G, D), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(jnp.asarray(kv_len, jnp.int32).reshape(1), q, k, v)
