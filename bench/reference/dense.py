"""Plain reference of a dense decoder: float32 ``jax.numpy``, no kernels,
no cache, no batching of its own beyond rows of equal length.

It follows the published layer equations: pre-norm RMSNorm, attention with
QKV bias and grouped KV heads (query head h reads KV head h // G), rotary
embedding on the whole head (``default``) or its first half (``half``,
ChatGLM), SwiGLU feed-forward, a final RMSNorm and an untied output head.
Rotary pairs dimension i with i + n/2 of the rotated width (the rotate-half
form); ChatGLM's own code pairs neighbours (2i, 2i+1), which is the same
model up to a fixed permutation of the q and k columns, so under weights
drawn at random it is the same model.

It imports nothing of the program.  Weights come from ``bench.weights``,
drawn from the seed again one layer at a time, upcast to float32, so the
whole model is never held at once.  Matmuls run at ``highest`` precision
(on a TPU a float32 matmul is otherwise done in bfloat16).

``quant="fp8"`` is the control: every linear layer's weights and inputs
rounded to float8 e4m3 (per output column and per row), the precision a
later change might serve in.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import weights as W

F8_MAX = 448.0  # largest finite float8_e4m3fn


def _round_fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _round_fp8(x, -1), _round_fp8(w, 0)
    return x @ w


def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, positions, kind, theta):
    """x [N, T, H, D]; positions [T]."""
    D = x.shape[-1]
    n = D if kind == "default" else D // 2
    freqs = 1.0 / theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = positions.astype(jnp.float32)[:, None] * freqs  # [T, n/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2, rest = x[..., : n // 2], x[..., n // 2 : n], x[..., n:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _layer_rows(m, quant, x, lw):
    """One decoder layer over rows x [N, T, d] (causal over T)."""
    N, T, _ = x.shape
    H, KV, D = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    a = lw["attn"]
    h = _rmsnorm(x, lw["ln1"], eps)
    q, k, v = (_linear(h, a[w], quant) for w in ("wq", "wk", "wv"))
    if m["qkv_bias"]:
        q, k, v = (t + a[b].astype(jnp.float32) for t, b in ((q, "bq"), (k, "bk"), (v, "bv")))
    pos = jnp.arange(T)
    q = _rope(q.reshape(N, T, H, D), pos, m["rope"], m["rope_theta"])
    k = _rope(k.reshape(N, T, KV, D), pos, m["rope"], m["rope_theta"])
    v = v.reshape(N, T, KV, D)
    q = q.reshape(N, T, KV, H // KV, D)
    s = jnp.einsum("ntkgd,nskd->nkgts", q, k) / jnp.sqrt(jnp.float32(D))
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = jnp.einsum("nkgts,nskd->ntkgd", jax.nn.softmax(s, axis=-1), v).reshape(N, T, H * D)
    x = x + _linear(o, a["wo"], quant)
    h = _rmsnorm(x, lw["ln2"], eps)
    f = lw["mlp"]
    g = jax.nn.silu(_linear(h, f["wi_gate"], quant)) * _linear(h, f["wi_up"], quant)
    return x + _linear(g, f["wo"], quant)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(mf, quant, block, x, lw):
    m = dict(mf)
    xb = x.reshape(-1, block, *x.shape[1:])
    with jax.default_matmul_precision("highest"):
        out = jax.lax.map(lambda r: _layer_rows(m, quant, r, lw), xb)
    return out.reshape(x.shape)


@partial(jax.jit, static_argnums=(0, 1))
def _embed(mf, V, tokens, embed):
    return jnp.take(embed[:V].astype(jnp.float32), tokens, axis=0)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _head(mf, quant, V, x, lt):
    m = dict(mf)
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x, lt["final_norm"], m["norm_eps"])
        return _linear(h, lt["lm_head"][:, :V], quant)


def logits(m: dict, seed_key, vocab_rows: int, tokens, first: int, quant=None, block=1):
    """float32 logits [N, T - first, V] at positions first .. T-1 of token
    rows [N, T], computed layer by layer in row blocks of ``block``."""
    mf = W._freeze(m)
    V = m["vocab_size"]
    dt = m["dtype"]
    top = W.top(m, seed_key, vocab_rows, dt)
    x = _embed(mf, V, jnp.asarray(tokens, jnp.int32), top["embed"])
    for i in range(m["n_layers"]):
        x = _layer(mf, quant, block, x, W.layer(m, seed_key, i, dt))
    return _head(mf, quant, V, x[:, first:], top)
