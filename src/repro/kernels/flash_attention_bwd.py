"""flash_attention backward kernels (the flash-attention-2 backward pass).

Residuals from the forward: q, k, v, o, lse (= m + log l per query row).
The host precomputes delta = rowsum(do * o).  Two kernels:

  * ``_dkdv_kernel`` — grid (BH, n_kv, n_q): for each kv block, stream the
    q/do blocks past it, recompute p = exp(s - lse), accumulate
    dv += p^T do and dk += ds^T q in VMEM scratch;
  * ``_dq_kernel``   — grid (BH, n_q, n_kv): for each q block, stream the
    kv blocks, accumulate dq += ds k.

Scores/probs/ds never touch HBM.  GQA: both kernels run per QUERY head
(kv blocks fetched via the h // G index map); the wrapper sums dk/dv over
each kv head's query group.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _recompute_p_ds(q, k, v, do, lse, delta, scale, causal, q_offset, qi, kj, bq, bk):
    """Shared recomputation: returns (p, ds), both [bq, bk] f32."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_offset + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    p = jnp.exp(s - lse)  # lse, delta: [bq, 1]
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    return p, ds


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_acc, dv_acc, *,
                 bq: int, bk: int, scale: float, causal: bool, q_offset: int, n_q: int):
    j = pl.program_id(1)  # kv block (outer)
    i = pl.program_id(2)  # q block (inner, accumulated)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    p, ds = _recompute_p_ds(
        q_ref[...], k_ref[...], v_ref[...], do_ref[...], lse_ref[...], delta_ref[...],
        scale, causal, q_offset, i, j, bq, bk,
    )
    dv_acc[...] += jax.lax.dot_general(
        p.astype(do_ref.dtype), do_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dk_acc[...] += jax.lax.dot_general(
        ds.astype(q_ref.dtype), q_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(i == n_q - 1)
    def _flush():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc, *,
               bq: int, bk: int, scale: float, causal: bool, q_offset: int, n_kv: int):
    i = pl.program_id(1)  # q block (outer)
    j = pl.program_id(2)  # kv block (inner, accumulated)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    _, ds = _recompute_p_ds(
        q_ref[...], k_ref[...], v_ref[...], do_ref[...], lse_ref[...], delta_ref[...],
        scale, causal, q_offset, i, j, bq, bk,
    )
    dq_acc[...] += jax.lax.dot_general(
        ds.astype(k_ref.dtype), k_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == n_kv - 1)
    def _flush():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def flash_attention_bwd_kernel(q, k, v, do, lse, delta, *, causal: bool,
                               q_offset: int = 0, block_q: int = 128,
                               block_k: int = 128, interpret: bool = True):
    """q/do [BH, Sq, D]; k/v [BKV, Sk, D]; lse/delta [BH, Sq, 1].

    Returns (dq [BH, Sq, D], dk_per_qhead [BH, Sk, D], dv_per_qhead
    [BH, Sk, D]) — the wrapper reduces dk/dv over each kv head's group."""
    BH, Sq, D = q.shape
    BKV, Sk, _ = k.shape
    G = BH // BKV
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    n_q, n_kv = Sq // bq, Sk // bk
    scale = 1.0 / (D**0.5)

    dkdv = pl.pallas_call(
        functools.partial(_dkdv_kernel, bq=bq, bk=bk, scale=scale, causal=causal,
                          q_offset=q_offset, n_q=n_q),
        grid=(BH, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda h, j, i: (h, i, 0)),  # q
            pl.BlockSpec((None, bk, D), lambda h, j, i, G=G: (h // G, j, 0)),  # k
            pl.BlockSpec((None, bk, D), lambda h, j, i, G=G: (h // G, j, 0)),  # v
            pl.BlockSpec((None, bq, D), lambda h, j, i: (h, i, 0)),  # do
            pl.BlockSpec((None, bq, 1), lambda h, j, i: (h, i, 0)),  # lse
            pl.BlockSpec((None, bq, 1), lambda h, j, i: (h, i, 0)),  # delta
        ],
        out_specs=[
            pl.BlockSpec((None, bk, D), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((None, bk, D), lambda h, j, i: (h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(q, k, v, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, scale=scale, causal=causal,
                          q_offset=q_offset, n_kv=n_kv),
        grid=(BH, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda h, i, j: (h, i, 0)),  # q
            pl.BlockSpec((None, bk, D), lambda h, i, j, G=G: (h // G, j, 0)),  # k
            pl.BlockSpec((None, bk, D), lambda h, i, j, G=G: (h // G, j, 0)),  # v
            pl.BlockSpec((None, bq, D), lambda h, i, j: (h, i, 0)),  # do
            pl.BlockSpec((None, bq, 1), lambda h, i, j: (h, i, 0)),  # lse
            pl.BlockSpec((None, bq, 1), lambda h, i, j: (h, i, 0)),  # delta
        ],
        out_specs=pl.BlockSpec((None, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)
    return dq, dkdv[0], dkdv[1]
