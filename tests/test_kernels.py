"""Per-kernel validation: interpret-mode Pallas vs the pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _tol(dtype):
    return TOL[jnp.bfloat16] if dtype == jnp.bfloat16 else TOL[jnp.float32]


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Sq,Sk,H,KV,D", [
    (1, 128, 128, 4, 4, 64),
    (2, 128, 256, 4, 2, 64),
    (1, 256, 256, 8, 1, 128),
    (2, 64, 64, 2, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(B, Sq, Sk, H, KV, D, dtype, causal):
    if causal and Sq != Sk:
        pytest.skip("causal requires aligned q/k positions in this sweep")
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, Sq, H, D), dtype)
    k = jnp.asarray(rng.randn(B, Sk, KV, D), dtype)
    v = jnp.asarray(rng.randn(B, Sk, KV, D), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_flash_attention_q_offset_decode_chunk():
    """Chunked prefill: queries at offset within the kv sequence."""
    rng = np.random.RandomState(1)
    B, Sq, Sk, H, D = 1, 64, 256, 2, 64
    q = jnp.asarray(rng.randn(B, Sq, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, Sk, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, Sk, H, D), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, q_offset=192, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=True, q_offset=192)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,S,H,KV,D", [(1, 128, 2, 2, 64), (2, 256, 4, 2, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_grads_match_ref(B, S, H, KV, D, causal):
    """The flash backward kernels (dk/dv and dq) give the oracle's
    gradients, GQA group sums included."""
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(B, S, n, D), jnp.float32) for n in (H, KV, KV))
    w = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    loss = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) * w)
    got = jax.grad(loss(lambda q, k, v: ops.flash_attention_trainable(q, k, v, causal)),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=causal)),
                    argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,KV,D,kv_len", [
    (1, 512, 4, 4, 64, 512),
    (2, 512, 8, 2, 64, 300),
    (1, 1024, 4, 1, 128, 7),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(B, S, H, KV, D, kv_len, dtype):
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(B, H, D), dtype)
    k = jnp.asarray(rng.randn(B, S, KV, D), dtype)
    v = jnp.asarray(rng.randn(B, S, KV, D), dtype)
    got = ops.decode_attention(q, k, v, kv_len, block_k=128)
    want = ref.decode_attention_ref(q, k, v, kv_len)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


# ---------------------------------------------------------------------------
# prefetch gather
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N,D,B", [(64, 128, 8), (1000, 384, 17), (16, 130, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefetch_gather_matches_ref(N, D, B, dtype):
    rng = np.random.RandomState(3)
    table = jnp.asarray(rng.randn(N, D), dtype)
    idx = jnp.asarray(rng.randint(0, N, size=B), jnp.int32)
    got = ops.prefetch_gather(table, idx)
    want = ref.prefetch_gather_ref(table, idx)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32))


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(2, 64),
    b=st.integers(1, 16),
    data=st.data(),
)
def test_prefetch_gather_property(n, b, data):
    """Hint-driven gather == direct indexing, for any hint set."""
    idx = data.draw(st.lists(st.integers(0, n - 1), min_size=b, max_size=b))
    table = jnp.arange(n * 128, dtype=jnp.float32).reshape(n, 128)
    got = ops.prefetch_gather(table, jnp.asarray(idx, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(table)[idx])


# ---------------------------------------------------------------------------
# rglru scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,W", [(1, 128, 256), (2, 64, 128), (3, 256, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_matches_ref(B, S, W, dtype):
    rng = np.random.RandomState(4)
    a = jnp.asarray(rng.uniform(0.5, 0.99, size=(B, S, W)), dtype)
    g = jnp.asarray(0.1 * rng.randn(B, S, W), dtype)
    got = ops.rglru_scan(a, g, block_s=32, block_m=128)
    # oracle over the folded layout
    want = jax.vmap(lambda aa, gg: ref.rglru_scan_ref(aa, gg))(a, g)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@settings(max_examples=20, deadline=None)
@given(s=st.integers(2, 32), seed=st.integers(0, 2**16))
def test_rglru_zero_decay_returns_input(s, seed):
    """Property: a == 0 -> h_t == g_t exactly."""
    rng = np.random.RandomState(seed)
    g = jnp.asarray(rng.randn(1, s, 128), jnp.float32)
    a = jnp.zeros_like(g)
    y = ops.rglru_scan(a, g, block_s=max(1, s // 2), block_m=128)
    np.testing.assert_allclose(np.asarray(y), np.asarray(g), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# mamba scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,C,N", [(1, 64, 256, 16), (2, 32, 128, 8), (1, 128, 512, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba_scan_matches_ref(B, S, C, N, dtype):
    rng = np.random.RandomState(5)
    dA = jnp.asarray(rng.uniform(0.3, 0.99, size=(B, S, C, N)), dtype)
    dBu = jnp.asarray(0.1 * rng.randn(B, S, C, N), dtype)
    Cm = jnp.asarray(rng.randn(B, S, N), dtype)
    got = ops.mamba_scan(dA, dBu, Cm, block_s=16, block_c=64)
    want = jax.vmap(ref.mamba_scan_ref)(dA, dBu, Cm)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_mamba_single_step_property(seed):
    """Property: with S == 1, y = (dBu . C) (h0 = 0)."""
    rng = np.random.RandomState(seed)
    dA = jnp.asarray(rng.rand(1, 1, 128, 8), jnp.float32)
    dBu = jnp.asarray(rng.randn(1, 1, 128, 8), jnp.float32)
    Cm = jnp.asarray(rng.randn(1, 1, 8), jnp.float32)
    y = ops.mamba_scan(dA, dBu, Cm, block_s=1, block_c=128)
    want = np.einsum("cn,n->c", np.asarray(dBu[0, 0]), np.asarray(Cm[0, 0]))
    np.testing.assert_allclose(np.asarray(y[0, 0]), want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# model-layer consistency: the chunked jnp attention (what the dry-run
# lowers) agrees with the Pallas kernel and the naive reference
# ---------------------------------------------------------------------------


def test_model_chunked_attention_agrees_with_kernel():
    from repro.models.layers import gqa_attention

    rng = np.random.RandomState(6)
    B, S, H, KV, D = 2, 128, 4, 2, 64
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, KV, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, KV, D), jnp.float32)
    a_model = gqa_attention(q, k, v, causal=True, impl="chunked", chunk=32)
    a_kernel = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(a_model), np.asarray(a_kernel), rtol=2e-4, atol=2e-4)
