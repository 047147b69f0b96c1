"""The serving path on the CPU: the Server's weight dtype and cache length,
generate against its own steps, and the Pallas attention path (interpret
mode) against the jnp path through prefill and decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.serve import KV_BLOCK, Server, pad_cache
from repro.launch.steps import concrete_batch, make_decode_step, make_prefill_step
from repro.models.transformer import kv_major


@pytest.fixture(scope="module")
def server():
    return Server(get_smoke_config("qwen1_5_4b"), max_len=40)


def test_server_holds_weights_in_compute_dtype(server):
    assert server.cfg.param_dtype == server.cfg.compute_dtype == "bfloat16"
    params = server.model.init_params(jax.random.PRNGKey(0))
    assert {x.dtype for x in jax.tree.leaves(params)} == {jnp.dtype(jnp.bfloat16)}


@pytest.mark.parametrize("max_len,want", [(40, 128), (128, 128), (129, 256), (544, 640)])
def test_server_rounds_cache_to_kv_block(max_len, want):
    s = Server(get_smoke_config("qwen1_5_4b"), max_len=max_len)
    assert s.max_len == want and s.max_len % KV_BLOCK == 0


def test_server_keeps_jnp_attention_off_tpu(server):
    assert jax.default_backend() != "tpu" and server.cfg.attn_impl == "chunked"


def test_generate_follows_its_own_steps(server):
    params = server.model.init_params(jax.random.PRNGKey(0))
    batch = {"inputs": concrete_batch(server.cfg, 2, 24)["inputs"]}
    tokens = server.generate(params, batch, 6)
    assert tokens.shape == (2, 6)
    logits, cache = server.prefill(params, batch)
    assert cache["k"].shape[2] == server.max_len
    want = [jnp.argmax(logits, -1)]
    for i in range(5):
        logits, cache = server.decode(params, cache, tokens[:, i : i + 1], 24 + i)
        want.append(jnp.argmax(logits, -1))
    np.testing.assert_array_equal(np.asarray(tokens), np.concatenate(want, axis=1))


def test_pallas_path_agrees_with_jnp_path():
    """attn_impl="pallas" (flash prefill + flash-decode, interpret mode on
    the CPU) gives the jnp path's logits through prefill and decode."""
    base = get_smoke_config("qwen1_5_4b").replace(param_dtype="float32", compute_dtype="float32")
    S, L = 128, 256  # both kernels need 128-multiples: prefill length, cache length
    out = {}
    for impl in ("pallas", "chunked"):
        cfg = base.replace(attn_impl=impl)
        model, prefill = make_prefill_step(cfg)
        _, decode = make_decode_step(cfg)
        params = model.init_params(jax.random.PRNGKey(1))  # same seed: same weights
        batch = {"inputs": concrete_batch(cfg, 2, S)["inputs"]}
        logits, cache = jax.jit(prefill)(params, batch)
        cache = pad_cache(cfg, cache, L)
        steps = [logits]
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for i in range(3):
            logits, cache = jax.jit(decode)(params, cache, tok, S + i)
            steps.append(logits)
            tok = (tok + 7) % cfg.vocab_size  # the same fed tokens on both paths
        out[impl] = np.stack([np.asarray(x) for x in steps])
    np.testing.assert_allclose(out["pallas"], out["chunked"], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("impl", ["pallas", "chunked"])
def test_decode_writes_each_position_in_place(impl):
    """After N decode steps the cache holds, at every written position, what
    a prefill of the prompt plus the generated tokens writes there, and
    nothing past them; the tokens are those of greedy decoding that
    recomputes the whole sequence on the naive path, with no cache."""
    cfg = get_smoke_config("qwen1_5_4b").replace(
        param_dtype="float32", compute_dtype="float32", attn_impl=impl)
    S, N, L = 128, 4, 256
    model, prefill = make_prefill_step(cfg)
    _, decode = make_decode_step(cfg)
    prefill, decode = jax.jit(prefill), jax.jit(decode, donate_argnums=(1,))
    params = model.init_params(jax.random.PRNGKey(2))
    prompt = concrete_batch(cfg, 2, S)["inputs"]
    logits, cache = prefill(params, {"inputs": prompt})
    cache = pad_cache(cfg, cache, L)
    tokens = [jnp.argmax(logits, -1).astype(jnp.int32)]
    for i in range(N):
        logits, cache = decode(params, cache, tokens[-1], S + i)
        tokens.append(jnp.argmax(logits, -1).astype(jnp.int32))
    seq = jnp.concatenate([prompt, *tokens[:-1]], axis=1)  # what the cache has seen
    _, want = prefill(params, {"inputs": seq})
    axis = 3 if kv_major(cfg) else 2  # the cache's seq axis
    assert cache["k"].shape[axis] == L and want["k"].shape[axis] == S + N
    for key in ("k", "v"):
        written = np.split(np.asarray(cache[key]), [S + N], axis=axis)
        np.testing.assert_allclose(written[0], np.asarray(want[key]), rtol=1e-4, atol=1e-4)
        assert not written[1].any()

    _, naive = make_prefill_step(cfg.replace(attn_impl="naive"))
    naive = jax.jit(naive)
    seq, want_tokens = prompt, []
    for _ in range(N + 1):
        tok = jnp.argmax(naive(params, {"inputs": seq})[0], -1).astype(jnp.int32)
        want_tokens.append(tok)
        seq = jnp.concatenate([seq, tok], axis=1)
    np.testing.assert_array_equal(np.concatenate(tokens, 1), np.concatenate(want_tokens, 1))


@pytest.mark.parametrize("arch", ["recurrentgemma_2b", "whisper_large_v3"])
def test_kv_major_cache_agrees_with_scan_order(arch):
    """The ring cache (recurrentgemma) and the self-attention cache beside
    the cross cache (whisper) decode to the same logits stored kv-major, as
    the Pallas path keeps them, and in the layer scan's order."""
    out = {}
    for impl in ("pallas", "chunked"):
        cfg = get_smoke_config(arch).replace(
            param_dtype="float32", compute_dtype="float32", attn_impl=impl)
        model, prefill = make_prefill_step(cfg)
        _, decode = make_decode_step(cfg)
        params = model.init_params(jax.random.PRNGKey(1))
        batch = concrete_batch(cfg, 2, 128)
        batch.pop("targets", None)
        logits, cache = jax.jit(prefill)(params, batch)
        cache = pad_cache(cfg, cache, 256)
        assert ({k: x.shape for k, x in cache.items()}
                == {k: x.shape for k, x in model.abstract_cache(2, 256).items()})
        steps, tok = [logits[:, -1]], jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        for i in range(3):
            logits, cache = jax.jit(decode)(params, cache, tok, 128 + i)
            steps.append(logits[:, -1])
            tok = (tok + 7) % cfg.vocab_size  # the same fed tokens on both paths
        out[impl] = np.stack([np.asarray(x) for x in steps])
    np.testing.assert_allclose(out["pallas"], out["chunked"], rtol=2e-4, atol=2e-4)
