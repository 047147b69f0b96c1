"""A whole run on the CPU at a tiny size, the chip look skipped, with the
served path broken underneath: ``correct`` has to come out false for each
fault a served cell can have, and true with none.

The tiny cells' limit (0.05 logits) sits between the sound runs' widest
gap on the CPU (under 0.001) and what a wrong token costs (tenths)."""

import time

import jax
import jax.numpy as jnp
import pytest

import tinycell
from bench import harness

LIMIT = 0.05
CELL = "tiny-chatglm.tiny_decode"


isolated_cache = pytest.fixture(scope="module", autouse=True)(tinycell.isolated_cache_fixture)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinycell.make_root(tmp_path_factory.mktemp("bench"), limit=LIMIT, sample=4)


def stale_cache(prefill, decode, argmax):
    """The decode step hands back the cache it was given."""
    def step(params, cache, tok, pos):
        logits, _ = decode(params, jax.tree.map(jnp.copy, cache), tok, pos)
        return logits, cache
    return prefill, step, argmax


def half_batch(prefill, decode, argmax):
    """The second half of the batch gets the first half's outputs."""
    def step(params, cache, tok, pos):
        logits, cache = decode(params, cache, tok, pos)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:h]]), cache
    return prefill, step, argmax


def altered_token(prefill, decode, argmax):
    """Every third token picked is one id off."""
    calls = [0]

    def pick(logits):
        calls[0] += 1
        tok = argmax(logits)
        return (tok + 1) % logits.shape[-1] if calls[0] % 3 == 0 else tok
    return prefill, decode, pick


def _run(root, fault):
    return harness.run_cell(CELL, 2**33 + 9, 1.0, False, time.perf_counter(), root=root,
                            fault=fault, need_chip=False)


def test_sound_run_is_correct(root):
    out = _run(root, None)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks" and out["checks"]["logit_gap"]["limit"] == LIMIT
    assert {"tpot_p95_ms", "setup_s"} <= set(out["metrics"])


@pytest.mark.parametrize("fault", [stale_cache, half_batch, altered_token],
                         ids=lambda f: f.__name__)
def test_broken_path_is_not_correct(root, fault):
    out = _run(root, fault)
    assert not out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] > LIMIT
