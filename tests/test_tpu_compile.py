"""Compile for a described TPU v5e, without a chip: the Pallas kernels at
qwen1.5-4b widths with ``interpret=False``, and the served full-width bf16
prefill and decode steps with their memory analysis.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and it keeps it until
it exits.  All such compiles live in this one file, so one test worker
loads the library.  Nothing runs: these prove the compiler accepts the
programs, not what they compute or how fast.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# qwen1.5-4b serving widths (B=4 prompts of 512, cache 640)
B, H, KV, D, SQ, SC = 4, 20, 20, 128, 512, 640
HBM_BYTES = 15.75 * 2**30  # what the compiler reports a v5e chip can hold


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_decode_attention_compiles(one_chip):
    from repro.kernels.decode_attention import decode_attention_kernel

    s = lambda shape, dt=jnp.bfloat16: _spec(one_chip, shape, dt)
    c = _compile(
        lambda q, k, v, n: decode_attention_kernel(q, k, v, n, block_k=128, interpret=False),
        s((B * KV, H // KV, D)), s((B * KV, SC, D)), s((B * KV, SC, D)), s((), jnp.int32),
    )
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("with_lse", [False, True])
def test_flash_fwd_compiles(one_chip, with_lse):
    from repro.kernels.flash_attention import flash_attention_kernel

    s = lambda shape: _spec(one_chip, shape)
    c = _compile(
        lambda q, k, v: flash_attention_kernel(q, k, v, causal=True, interpret=False,
                                               with_lse=with_lse),
        s((B * H, SQ, D)), s((B * KV, SQ, D)), s((B * KV, SQ, D)),
    )
    assert "tpu_custom_call" in c.as_text()


def test_flash_bwd_compiles(one_chip):
    from repro.kernels.flash_attention_bwd import flash_attention_bwd_kernel

    s = lambda shape, dt=jnp.bfloat16: _spec(one_chip, shape, dt)
    c = _compile(
        lambda q, k, v, do, lse, delta: flash_attention_bwd_kernel(
            q, k, v, do, lse, delta, causal=True, interpret=False),
        s((B * H, SQ, D)), s((B * KV, SQ, D)), s((B * KV, SQ, D)), s((B * H, SQ, D)),
        s((B * H, SQ, 1), jnp.float32), s((B * H, SQ, 1), jnp.float32),
    )
    assert c.as_text().count("tpu_custom_call") >= 2  # dk/dv and dq kernels


def test_prefetch_gather_compiles(one_chip):
    from repro.kernels.prefetch_gather import prefetch_gather_kernel

    c = _compile(
        lambda t, i: prefetch_gather_kernel(t, i, interpret=False),
        _spec(one_chip, (151_936, 2560)), _spec(one_chip, (64,), jnp.int32),
    )
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_compiles(one_chip, dtype):
    from repro.kernels.rglru_scan import rglru_scan_kernel

    a = _spec(one_chip, (512, 2560), dtype)  # recurrentgemma-2b lru width
    c = _compile(lambda a, g: rglru_scan_kernel(a, g, interpret=False), a, a)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba_scan_compiles(one_chip, dtype):
    from repro.kernels.mamba_scan import mamba_scan_kernel

    x = _spec(one_chip, (256, 8192, 16), dtype)  # falcon-mamba-7b d_inner, state
    c = _compile(lambda a, b, cm: mamba_scan_kernel(a, b, cm, interpret=False),
                 x, x, _spec(one_chip, (256, 16), dtype))
    assert "tpu_custom_call" in c.as_text()


@pytest.fixture(scope="module")
def served(one_chip):
    """The full-width qwen1.5-4b Server as it runs on a TPU (bf16 weights,
    Pallas attention) with abstract arguments placed on the described chip."""
    from repro.configs import get_config
    from repro.launch.serve import Server

    # the Server picks the kernels from the backend, which here is the CPU
    server = Server(get_config("qwen1_5_4b").replace(attn_impl="pallas"), max_len=SQ + 32)
    place = lambda tree: jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype), tree)
    params = place(server.model.abstract_params())
    cache = place(server.model.abstract_cache(B, server.max_len))
    return server, params, cache


@pytest.fixture
def compiled_kernels(monkeypatch):
    from repro.kernels import ops

    jax.clear_caches()  # no trace of the kernel wrappers made in interpret mode
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    yield
    jax.clear_caches()


def _fits(compiled) -> None:
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB of {HBM_BYTES / 2**30} GiB"


def test_full_width_decode_step_fits_one_chip(served, one_chip, compiled_kernels):
    server, params, cache = served
    assert server.cfg.param_dtype == "bfloat16" and server.max_len == 640
    c = server.decode.lower(
        params, cache, _spec(one_chip, (B, 1), jnp.int32), _spec(one_chip, (), jnp.int32)
    ).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


def test_full_width_prefill_step_fits_one_chip(served, one_chip, compiled_kernels):
    server, params, _ = served
    c = server.prefill.lower(params, {"inputs": _spec(one_chip, (B, SQ), jnp.int32)}).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c)
