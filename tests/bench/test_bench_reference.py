"""The benchmark's plain reference (bench/reference/dense.py) against the
program's model at both configurations' smoke sizes, in float32 on the CPU:
the prefill's last logits, then decoding through the cache, teacher-forced
on fixed tokens.  Same weights (bench/weights.py) on both sides."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tinycell  # noqa: F401  (puts the repository on sys.path)
from bench import weights
from bench.harness import model_block
from bench.reference import dense
from repro.configs import get_smoke_config
from repro.launch.serve import pad_cache
from repro.models.model import Model

P, STEPS, N = 12, 5, 3


@pytest.mark.parametrize("name", sorted(tinycell.TINY))
def test_reference_matches_program_prefill_and_decode(name):
    spec = tinycell.TINY[name]
    m = {**model_block(spec), "dtype": "float32"}
    cfg = get_smoke_config(spec["program_config"]).replace(
        compute_dtype="float32", param_dtype="float32")
    model = Model(cfg)
    rows = model.abstract_params()["embed"].shape[0]
    key = weights.root_key(2**35 + 3)
    params = weights.make_params(m, key, rows, "float32")
    tokens = np.random.default_rng(0).integers(0, m["vocab_size"], (N, P + STEPS), np.int32)

    want = np.asarray(dense.logits(m, key, rows, tokens, P - 1))  # [N, STEPS + 1, V]
    logits, cache = model.prefill(params, {"inputs": jnp.asarray(tokens[:, :P])})
    got = [logits[:, -1]]
    cache = pad_cache(cfg, dict(cache), P + STEPS)  # on the seq axis of the cache's order
    for i in range(STEPS):
        logits, cache = model.decode_step(params, cache, jnp.asarray(tokens[:, P + i : P + i + 1]),
                                          P + i)
        got.append(logits[:, -1])
    got = np.stack([np.asarray(g) for g in got], axis=1)
    scale = np.abs(want).max()
    assert scale > 0.5  # logits spread, not a degenerate model
    np.testing.assert_allclose(got, want, atol=2e-4 * scale, rtol=0)


def test_layer_by_layer_weights_equal_the_served_tree():
    m = model_block(tinycell.TINY["tiny-chatglm"])
    key = weights.root_key(7)
    tree = weights.make_params(m, key, 128, m["dtype"])
    for i in range(m["n_layers"]):
        one = weights.layer(m, key, i, m["dtype"])
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(a[i], b), tree["layers"], one)
    top = weights.top(m, key, 128, m["dtype"])
    for k in top:
        np.testing.assert_array_equal(tree[k], top[k])


def test_seeds_past_32_bits_differ():
    a, b = weights.root_key(5), weights.root_key(5 + 2**32)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_fp8_control_departs_from_the_reference():
    m = model_block(tinycell.TINY["tiny-qwen"])
    key = weights.root_key(11)
    tokens = np.random.default_rng(1).integers(0, m["vocab_size"], (2, 16), np.int32)
    f32 = np.asarray(dense.logits(m, key, 128, tokens, 8))
    f8 = np.asarray(dense.logits(m, key, 128, tokens, 8, quant="fp8"))
    err = np.abs(f8 - f32).max() / np.abs(f32).max()
    assert 1e-3 < err < 0.5


# sha256 over each leaf's path and bytes of make_params(m, root_key(2**33 +
# 5), 256, "bfloat16"), as drawn before the leaf tables moved into
# bench/reference/dense.py: the dense weights are the same bits.
DIGESTS = {
    "tiny-chatglm": "618f58b8f03b1b12139de974298c9c6efa1f2f7719f8171801530d07b747f639",
    "tiny-qwen": "13669583c162413304a21bafaff48a8278535251ffe57156f868d9a6d7504012",
}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_dense_weights_are_the_recorded_draw(name):
    m = model_block(tinycell.TINY[name])
    assert _digest(weights.make_params(m, weights.root_key(2**33 + 5), 256, "bfloat16")) \
        == DIGESTS[name]
