"""flash_attention — blocked causal attention with online softmax.

Grid: (batch*q_heads, Sq/bq, Sk/bk), KV innermost; the running max / sum /
accumulator live in VMEM scratch across the KV dimension, so KV blocks
stream HBM->VMEM through the Pallas pipeline (double-buffered) while the MXU
consumes the previous block.  GQA is handled without materializing repeated
KV heads: the KV BlockSpec index_map divides the query-head index by the
group size, so each KV head's blocks are fetched once per group.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *refs, bq: int, bk: int,
                  causal: bool, q_offset: int, n_kv: int, with_lse: bool):
    lse_ref = refs[0] if with_lse else None
    m_ref, l_ref, acc_ref = refs[-3:]
    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # kv block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]  # [bq, D]
    k = k_ref[...]  # [bk, D]
    v = v_ref[...]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (1.0 / (q.shape[-1] ** 0.5))
    if causal:
        qpos = q_offset + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    m_prev = m_ref[...]  # [bq, 1]
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
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        if with_lse:
            lse_ref[...] = m_ref[...] + jnp.log(l_safe)


def flash_attention_kernel(q, k, v, *, causal: bool = True, q_offset: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = True, with_lse: bool = False):
    """q [BH, Sq, D]; k, v [BKV, Sk, D] with BH % BKV == 0 -> [BH, Sq, D]
    (+ the log-sum-exp [BH, Sq, 1] when ``with_lse`` — the flash-backward
    residual)."""
    BH, Sq, D = q.shape
    BKV, Sk, _ = k.shape
    G = BH // BKV
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    n_kv = Sk // bk
    grid = (BH, Sq // bq, n_kv)
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, causal=causal, q_offset=q_offset, n_kv=n_kv,
        with_lse=with_lse,
    )
    kv_spec = pl.BlockSpec((None, bk, D), lambda h, i, j: (h // G, j, 0))
    out_specs = [pl.BlockSpec((None, bq, D), lambda h, i, j: (h, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((BH, Sq, D), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((None, bq, 1), lambda h, i, j: (h, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda h, i, j: (h, i, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return tuple(outs) if with_lse else outs[0]
