"""Operations and bytes that the work of a decoder needs, from its shapes
alone (the least the chip must do, not what the program does).

``m`` is a configuration's ``model`` block with its ``reference`` name
(``Cell.model``).  A layer's matmul parameters and the weight bytes a
decode step reads are its family's (``bench/reference/<family>.py``);
attention, the KV cache and the head's FLOPs are counted here.  Weights
are held in ``m["dtype"]`` (2 bytes for bfloat16) and so is the KV cache.
Matmul FLOPs count 2 per multiply-add; causal attention counts only the
keys at or before each query.
"""

from __future__ import annotations

import jax.numpy as jnp

from bench.reference import family


def _width(m: dict) -> int:
    return jnp.dtype(m["dtype"]).itemsize


def layer_matmul_params(m: dict) -> int:
    """Matmul parameters one token passes through in one layer."""
    return family(m).layer_matmul_params(m)


def layer_params(m: dict) -> int:
    """Every parameter of one layer."""
    return family(m).layer_params(m)


def kv_bytes_per_token(m: dict) -> int:
    """K and V of one position, all layers."""
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * _width(m)


def attention_flops(m: dict, batch: int, q_len: int, kv_lens) -> int:
    """Scores and weighted values (2 matmuls) for one layer: query i of
    ``q_len`` reads ``kv_lens[i]`` keys."""
    return 4 * batch * m["n_heads"] * m["head_dim"] * int(sum(kv_lens))


def prefill_flops(m: dict, batch: int, seq: int) -> int:
    """One prefill of ``batch`` prompts of ``seq`` tokens, with the logits
    of each prompt's last position."""
    tokens = batch * seq
    causal = attention_flops(m, batch, seq, range(1, seq + 1))
    return (m["n_layers"] * (2 * tokens * layer_matmul_params(m) + causal)
            + 2 * batch * m["d_model"] * m["vocab_size"])


def decode_flops(m: dict, batch: int, kv_len: float) -> float:
    """One decode step of ``batch`` tokens, each attending ``kv_len`` keys
    (its own included)."""
    per_layer = 2 * batch * layer_matmul_params(m) + attention_flops(m, batch, 1, [kv_len])
    return m["n_layers"] * per_layer + 2 * batch * m["d_model"] * m["vocab_size"]


def decode_min_bytes(m: dict, batch: int, kv_len: float) -> float:
    """HBM bytes one decode step must move at least: the weights its family
    says a step of ``batch`` tokens reads, K/V read up to ``kv_len`` (the new
    position included) and the new K/V written."""
    weights = family(m).decode_weight_bytes(m, batch)
    return weights + kv_bytes_per_token(m) * batch * kv_len


def decode_attention_call(m: dict, batch: int, kv_len: float) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's decode attention: q in, o out, K and V
    read up to ``kv_len``."""
    w = _width(m)
    H, KV, D = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    nbytes = 2 * batch * H * D * w + 2 * batch * kv_len * KV * D * w
    return attention_flops(m, batch, 1, [kv_len]), nbytes


def flash_fwd_call(m: dict, batch: int, seq: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one layer's causal prefill attention: q, k, v in,
    o out."""
    w = _width(m)
    H, KV, D = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    nbytes = (2 * batch * seq * H * D + 2 * batch * seq * KV * D) * w
    return attention_flops(m, batch, seq, range(1, seq + 1)), nbytes
