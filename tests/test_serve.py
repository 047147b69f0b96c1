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
