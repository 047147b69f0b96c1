"""bench/flops.py against counts made by hand at a small shape, and against
XLA's own count of the reference's matmuls."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tinycell  # noqa: F401
from bench import flops, harness

# 2 layers, d 8, 2 query heads and 1 KV head of 4, d_ff 16, vocab 10
M = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1, "head_dim": 4, "d_ff": 16,
     "vocab_size": 10, "qkv_bias": True, "dtype": "bfloat16", "reference": "dense"}

# The dense counts at the cells' shapes, as the benchmark read them before
# the family's counts moved into bench/reference/dense.py: prefill_flops(m,
# 4, 1024), decode_flops, decode_min_bytes and decode_attention_call at
# (batch, 896), flash_fwd_call(m, 4, 1024).
PINNED = {
    "qwen1.5-4b.decode": (8, 26_847_496_110_080, 59_910_389_760, 10_058_880_000,
                          (73_400_320, 73_482_240), (21_495_808_000, 83_886_080)),
    "chatglm3-6b.decode": (16, 47_745_926_692_864, 197_836_931_072, 12_365_664_256,
                           (234_881_024, 14_942_208), (34_393_292_800, 71_303_168)),
}


def test_parameter_counts():
    # wq 8x8, wk and wv 8x4, wo 8x8, three 8x16 matrices
    assert flops.layer_matmul_params(M) == 64 + 32 + 32 + 64 + 384
    # + biases (8 + 4 + 4) + two norms (8 + 8)
    assert flops.layer_params(M) == 576 + 16 + 16
    assert flops.kv_bytes_per_token(M) == 2 * 2 * 1 * 4 * 2  # k+v, layers, kv, head, bf16


def test_prefill_flops_by_hand():
    # batch 1, 3 tokens: matmuls 2*3*576 per layer; causal attention reads
    # 1+2+3 keys, 4 flops per key per head dim per head: 4*2*4*6; head on 1
    # position: 2*8*10
    per_layer = 2 * 3 * 576 + 4 * 2 * 4 * 6
    assert flops.prefill_flops(M, 1, 3) == 2 * per_layer + 2 * 8 * 10


def test_decode_flops_and_bytes_by_hand():
    # batch 2 at kv_len 5
    per_layer = 2 * 2 * 576 + 4 * 2 * 2 * 4 * 5
    assert flops.decode_flops(M, 2, 5) == 2 * per_layer + 2 * 2 * 8 * 10
    weights = (2 * 608 + 8 * 10 + 8 + 2 * 8) * 2
    assert flops.decode_min_bytes(M, 2, 5) == weights + 32 * 2 * 5


def test_kernel_calls_by_hand():
    f, b = flops.decode_attention_call(M, 2, 5)
    assert f == 4 * 2 * 2 * 4 * 5
    assert b == (2 * 2 * 2 * 4 + 2 * 2 * 5 * 1 * 4) * 2  # q and o; k and v
    f, b = flops.flash_fwd_call(M, 1, 3)
    assert f == 4 * 1 * 2 * 4 * 6
    assert b == (2 * 3 * 2 * 4 + 2 * 3 * 1 * 4) * 2


def test_matmul_flops_agree_with_xla():
    """One token through one layer's matmuls: XLA's count of the plain
    products equals the closed form (attention and head aside)."""
    d, f = M["d_model"], M["d_ff"]
    q, kv = 8, 4
    shapes = [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]
    ws = [jnp.ones(s, jnp.float32) for s in shapes]

    def layer(x, ws):
        a = x @ ws[0] + 0 * (x @ ws[1]).sum() + 0 * (x @ ws[2]).sum()
        x = x + a @ ws[3]
        return x + ((x @ ws[4]) * (x @ ws[5])) @ ws[6]

    cost = jax.jit(layer).lower(jnp.ones((1, d)), ws).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    matmul = 2 * flops.layer_matmul_params(M)
    assert matmul <= cost["flops"] < matmul * 1.25  # plus a few elementwise ops
    np.testing.assert_equal(flops.layer_matmul_params(M), sum(a * b for a, b in shapes))


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_dense_counts_at_the_cells_shapes_are_pinned(cell):
    m = harness.load_cell(cell).model
    B, prefill, decode, nbytes, attn, flash = PINNED[cell]
    assert flops.prefill_flops(m, 4, 1024) == prefill
    assert flops.decode_flops(m, B, 896) == decode
    assert flops.decode_min_bytes(m, B, 896) == nbytes
    assert flops.decode_attention_call(m, B, 896) == attn
    assert flops.flash_fwd_call(m, 4, 1024) == flash
