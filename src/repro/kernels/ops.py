"""jit'd public wrappers around the Pallas kernels.

Each wrapper accepts model-level layouts ([B, S, H, D] attention etc.),
folds them into the kernel layouts, picks interpret mode automatically
(interpret=True off-TPU so the kernels are validated on CPU), and exposes
the same signature as the pure-jnp oracle in ref.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention_kernel
from .flash_attention import flash_attention_kernel
from .mamba_scan import mamba_scan_kernel
from .prefetch_gather import prefetch_gather_kernel
from .rglru_scan import rglru_scan_kernel


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _fold_q(q):
    B, Sq, H, D = q.shape
    return q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)


def _unfold_q(qf, B, H):
    BH, Sq, D = qf.shape
    return qf.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("causal", "q_offset", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, q_offset=0, block_q=128, block_k=128):
    """q [B, Sq, H, D]; k, v [B, Sk, KV, D] -> [B, Sq, H, D]."""
    B, Sq, H, D = q.shape
    of = flash_attention_kernel(
        _fold_q(q), _fold_q(k), _fold_q(v), causal=causal, q_offset=q_offset,
        block_q=block_q, block_k=block_k, interpret=_interpret(),
    )
    return _unfold_q(of, B, H)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_trainable(q, k, v, causal=True, q_offset=0):
    """Differentiable flash attention: the Pallas forward kernel emits
    (o, lse); the backward runs the flash-attention-2 backward kernels
    (flash_attention_bwd.py) — scores/probs/ds never touch HBM in either
    direction."""
    return flash_attention(q, k, v, causal=causal, q_offset=q_offset)


def _fat_fwd(q, k, v, causal, q_offset):
    B, Sq, H, D = q.shape
    qf, kf, vf = _fold_q(q), _fold_q(k), _fold_q(v)
    of, lse = flash_attention_kernel(
        qf, kf, vf, causal=causal, q_offset=q_offset, interpret=_interpret(),
        with_lse=True,
    )
    return _unfold_q(of, B, H), (qf, kf, vf, of, lse, B, H)


def _fat_bwd(causal, q_offset, res, g):
    from .flash_attention_bwd import flash_attention_bwd_kernel

    qf, kf, vf, of, lse, B, H = res
    KV = kf.shape[0] // B
    G = H // KV
    dof = _fold_q(g)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1, keepdims=True)
    dq, dk_q, dv_q = flash_attention_bwd_kernel(
        qf, kf, vf, dof, lse, delta, causal=causal, q_offset=q_offset,
        interpret=_interpret(),
    )
    # reduce dk/dv over each kv head's query group (GQA)
    Sk, D = kf.shape[1], kf.shape[2]
    dk = dk_q.reshape(B, KV, G, Sk, D).sum(axis=2).reshape(B * KV, Sk, D)
    dv = dv_q.reshape(B, KV, G, Sk, D).sum(axis=2).reshape(B * KV, Sk, D)
    return _unfold_q(dq, B, H), _unfold_q(dk, B, KV), _unfold_q(dv, B, KV)


flash_attention_trainable.defvjp(_fat_fwd, _fat_bwd)


@partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q, k, v, kv_len, *, block_k=512):
    """q [B, H, D]; k, v [B, S, KV, D]; kv_len scalar -> [B, H, D]."""
    B, H, D = q.shape
    KV = k.shape[2]
    qf = q.reshape(B * KV, H // KV, D)  # heads are kv-major: head h reads kv h // G
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, -1, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, -1, D)
    S = kf.shape[1]
    bk = min(block_k, S)
    while S % bk:
        bk //= 2
    of = decode_attention_kernel(qf, kf, vf, kv_len, block_k=bk, interpret=_interpret())
    return of.reshape(B, H, D)


@partial(jax.jit, static_argnames=("block_d",))
def prefetch_gather(table, idx, *, block_d=512):
    """table [N, D]; idx [B] -> [B, D] (D padded to a lane multiple)."""
    N, D = table.shape
    pad = (-D) % 128
    if pad:
        table = jnp.pad(table, ((0, 0), (0, pad)))
    bd = min(block_d, table.shape[1])
    while table.shape[1] % bd:
        bd //= 2
    out = prefetch_gather_kernel(table, idx, block_d=max(bd, 128), interpret=_interpret())
    return out[:, :D]


@partial(jax.jit, static_argnames=("block_s", "block_m"))
def rglru_scan(a, g, *, block_s=256, block_m=512):
    """a, g [B, S, W] -> y [B, S, W] (h_0 = 0): batch folded into channels."""
    B, S, W = a.shape
    af = a.transpose(1, 0, 2).reshape(S, B * W)
    gf = g.transpose(1, 0, 2).reshape(S, B * W)
    bm = min(block_m, B * W)
    while (B * W) % bm:
        bm //= 2
    bs = min(block_s, S)
    while S % bs:
        bs //= 2
    y = rglru_scan_kernel(af, gf, block_s=bs, block_m=max(1, bm), interpret=_interpret())
    return y.reshape(S, B, W).transpose(1, 0, 2)


@partial(jax.jit, static_argnames=("block_s", "block_c"))
def mamba_scan(dA, dBu, C, *, block_s=16, block_c=256):
    """dA, dBu [B, S, Ch, N]; C [B, S, N] -> y [B, S, Ch] (vmapped batch)."""
    bs = min(block_s, dA.shape[1])
    while dA.shape[1] % bs:
        bs //= 2
    bc = min(block_c, dA.shape[2])
    while dA.shape[2] % bc:
        bc //= 2
    fn = partial(
        mamba_scan_kernel, block_s=max(1, bs), block_c=max(1, bc), interpret=_interpret()
    )
    return jax.vmap(fn)(dA, dBu, C)
