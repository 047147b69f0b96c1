"""Plain reference of a dense decoder: float32 ``jax.numpy``, no kernels,
no cache, no batching of its own beyond rows of equal length.

It follows the published layer equations: pre-norm RMSNorm, attention with
QKV bias and grouped KV heads (query head h reads KV head h // G), rotary
embedding on the whole head (``default``) or its first half (``half``,
ChatGLM), SwiGLU feed-forward, a final RMSNorm and the output head (the
embedding's transpose where the configuration ties them).  Rotary pairs
dimension i with i + n/2 of the rotated width (the rotate-half form);
ChatGLM's own code pairs neighbours (2i, 2i+1), which is the same model up
to a fixed permutation of the q and k columns, so under weights drawn at
random it is the same model.

It imports nothing of the program.  Weights come from ``bench.weights``,
drawn from the seed again one layer at a time, upcast to float32, so the
whole model is never held at once.  Matmuls run at ``highest`` precision
(on a TPU a float32 matmul is otherwise done in bfloat16).

``quant="fp8"`` is the control: every linear layer's weights and inputs
rounded to float8 e4m3 (per output column and per row), the precision a
later change might serve in.

The module is also the family's home in the benchmark (``bench.reference``):
its leaves, the program fields it must match, and its least work.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import weights as W

F8_MAX = 448.0  # largest finite float8_e4m3fn

# -- the family's leaves -----------------------------------------------------

# (name, init, scale) of each leaf, as bench.weights draws them; a leaf's
# place in its table is folded into its key, so the order is fixed.
LAYER = (
    ("ln1", "around_one", 0.1),
    ("ln2", "around_one", 0.1),
    ("attn.wq", "normal", None),
    ("attn.wk", "normal", None),
    ("attn.wv", "normal", None),
    ("attn.wo", "normal", None),
    ("attn.bq", "normal", 0.1),
    ("attn.bk", "normal", 0.1),
    ("attn.bv", "normal", 0.1),
    ("mlp.wi_gate", "normal", None),
    ("mlp.wi_up", "normal", None),
    ("mlp.wo", "normal", None),
)
TOP = (
    ("embed", "normal", 1.0),
    ("lm_head", "normal", None),
    ("final_norm", "around_one", 0.1),
)


def layer_shapes(m: dict) -> dict:
    d, f = m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    shapes = {
        "ln1": (d,), "ln2": (d,),
        "attn.wq": (d, q), "attn.wk": (d, kv), "attn.wv": (d, kv), "attn.wo": (q, d),
        "mlp.wi_gate": (d, f), "mlp.wi_up": (d, f), "mlp.wo": (f, d),
    }
    if m["qkv_bias"]:
        shapes.update({"attn.bq": (q,), "attn.bk": (kv,), "attn.bv": (kv,)})
    return shapes


def top_shapes(m: dict, vocab_rows: int) -> dict:
    """``vocab_rows``: the embedding rows the program holds (its vocab
    padded); rows past ``vocab_size`` are drawn but never read."""
    d = m["d_model"]
    shapes = {"embed": (vocab_rows, d), "final_norm": (d,)}
    if not m["tie_embeddings"]:
        shapes["lm_head"] = (d, vocab_rows)
    return shapes


def program_fields(m: dict) -> dict:
    """The program's ``ModelConfig`` fields that must equal the file's:
    every width, the rotary kind, biases and the served dtype."""
    return {
        "family": "dense", "norm": "rmsnorm", "mlp": "swiglu",
        "n_layers": m["n_layers"], "d_model": m["d_model"], "n_heads": m["n_heads"],
        "n_kv_heads": m["n_kv_heads"], "head_dim": m["head_dim"], "d_ff": m["d_ff"],
        "vocab_size": m["vocab_size"], "rope": m["rope"], "rope_theta": m["rope_theta"],
        "qkv_bias": m["qkv_bias"], "tie_embeddings": m["tie_embeddings"],
        "compute_dtype": m["dtype"], "attn_out_bias": False, "mlp_bias": False,
        "qk_norm": False,
    }


# -- the family's least work (bench.flops reads it) --------------------------


def layer_matmul_params(m: dict) -> int:
    """Matmul parameters one token passes through in one layer (its matmul
    FLOPs are twice this)."""
    d, f = m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def layer_params(m: dict) -> int:
    """Every parameter of one layer: matmuls, biases, two norms."""
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    bias = q + 2 * kv if m["qkv_bias"] else 0
    return layer_matmul_params(m) + bias + 2 * m["d_model"]


def decode_weight_bytes(m: dict, batch: int) -> int:
    """Weight bytes a decode step of ``batch`` tokens must read: every layer
    weight and the head once, the final norm, the batch's embedding rows."""
    d = m["d_model"]
    params = m["n_layers"] * layer_params(m) + d * m["vocab_size"] + d + batch * d
    return params * jnp.dtype(m["dtype"]).itemsize


# -- the reference --------------------------------------------------------------


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
        head = lt["lm_head"] if "lm_head" in lt else lt["embed"].T
        return _linear(h, head[:, :V], quant)


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
