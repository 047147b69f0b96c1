"""The served steps' instrumentation on the CPU: the named scopes change no
compiled op and land on the ops they name, ``Server``'s profiler spans
reach a trace, and ``Server.stats`` counts steps, compiles and the plan's
two halves."""

import contextlib
import re
import weakref
from collections import Counter, defaultdict

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.launch import serve
from repro.launch.serve import Server
from repro.obs import scopes

B, S = 2, 128  # the Pallas kernels (interpret mode here) need 128-multiples
# every scope the per-step times of bench/scope_trace.py add up
READ = {"decode": ("kv_write", "kv_layout", "layers", "embed", "qkv", "attn_out", "ffn", "logits"),
        "prefill": ("attn_core", "kv_layout")}


def _server(impl: str) -> Server:
    return Server(get_smoke_config("qwen1_5_4b").replace(attn_impl=impl), max_len=S + 2)


def _lowered(server: Server) -> dict:
    params = server.model.abstract_params()
    return {
        "prefill": server.prefill.lower(params, {"inputs": jax.ShapeDtypeStruct((B, S), jnp.int32)}),
        "decode": server.decode.lower(params, server.model.abstract_cache(B, server.max_len),
                                      jax.ShapeDtypeStruct((B, 1), jnp.int32),
                                      jax.ShapeDtypeStruct((), jnp.int32)),
    }


def _strip(hlo: str) -> str:
    """The compiled module without its metadata: each op's ``metadata={...}``,
    the source tables it points into, and the numbering of instruction names
    (which follows the ops' source locations): each name becomes the order
    in which it first appears."""
    hlo = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n", "\n", hlo,
                 flags=re.S)
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    names: dict = {}
    return re.sub(r"%[\w.-]+", lambda m: names.setdefault(m.group(), f"%{len(names)}"), hlo)


@pytest.mark.parametrize("impl", ["pallas", "chunked"])
def test_scopes_change_no_compiled_op(impl, monkeypatch):
    jax.clear_caches()  # the kernels' own jit caches hold scoped traces
    scoped = {k: v.compile().as_text() for k, v in _lowered(_server(impl)).items()}
    monkeypatch.setattr(scopes, "scope", lambda name: contextlib.nullcontext())
    jax.clear_caches()
    bare = {k: v.compile().as_text() for k, v in _lowered(_server(impl)).items()}
    for step in ("prefill", "decode"):
        assert "kv_write" in scoped[step] and "kv_write" not in bare[step]
        assert _strip(scoped[step]) == _strip(bare[step]), step


def _ops_by_scope(hlo: str) -> dict:
    """{scope: {opcode: count}}, each op under the innermost scope on its
    op_name path."""
    out = defaultdict(lambda: defaultdict(int))
    for opcode, name in re.findall(r"= \S+ ([\w-]+)\(.*op_name=\"([^\"]*)\"", hlo):
        inner = [p for p in name.split("/") if p in scopes.SCOPES]
        out[inner[-1] if inner else ""][opcode] += 1
    return out


def _shapes_by_scope(hlo: str, opcode: str) -> dict:
    """{scope: [dims of each ``opcode`` op's result, 1s dropped]}, each op
    under the innermost scope on its op_name path."""
    out = defaultdict(list)
    for dims, name in re.findall(rf"= \w+\[([\d,]*)\]\S* {opcode}\(.*op_name=\"([^\"]*)\"", hlo):
        inner = [p for p in name.split("/") if p in scopes.SCOPES]
        out[inner[-1] if inner else ""].append(tuple(int(d) for d in dims.split(",") if d != "1"))
    return out


def test_scopes_name_the_ops_they_cover():
    server = _server("pallas")
    lowered = _lowered(server)
    # before optimisation (a nested jit's ops carry their path from its own
    # root) and as compiled (inlined, with whole paths; the CPU compiler
    # folds the layout transposes away)
    hlo = {step: low.as_text(dialect="hlo", debug_info=True) for step, low in lowered.items()}
    ops = {step: _ops_by_scope(text) for step, text in hlo.items()}
    texts = {step: low.compile().as_text() for step, low in lowered.items()}
    compiled = {step: _ops_by_scope(text) for step, text in texts.items()}
    for step, names in READ.items():
        assert set(names) <= set(ops[step]) | set(compiled[step]), (step, sorted(ops[step]))
    # the new position written into the stacked cache, which the kernel
    # reads as it is stored: no transpose of the cache
    cache = server.model.abstract_cache(B, server.max_len)["k"].shape
    # an op's result holds a layer of the cache (in any order) or more
    holds_cache = lambda dims: Counter(d for d in cache[1:] if d != 1) <= Counter(dims)
    assert ops["decode"]["kv_write"]["dynamic-update-slice"] == 2
    assert ops["decode"]["kv_layout"]["transpose"] == 0
    assert not any(holds_cache(d) for ds in _shapes_by_scope(hlo["decode"], "transpose").values()
                   for d in ds)
    assert ops["prefill"]["kv_layout"]["transpose"] == 4  # q, k, v folded for flash_fwd, o unfolded
    assert compiled["prefill"]["kv_write"]["pad"] == 2  # the cache grown to its length
    # the layer scan slices each layer's weights out of their stacks, and
    # nothing out of the cache, which it carries whole
    assert compiled["decode"]["layers"]["dynamic-slice"] > 0
    sliced = _shapes_by_scope(texts["decode"], "dynamic-slice")["layers"]
    assert sliced and not any(holds_cache(d) for d in sliced)
    assert ops["decode"]["attn_out"]["add"] == 1  # the residual add
    assert compiled["decode"]["embed"]["gather"] == 1
    with pytest.raises(ValueError):
        scopes.scope("mlp")


@pytest.fixture(scope="module")
def served():
    server = _server("chunked")
    return server, server.model.init_params(jax.random.PRNGKey(0))


def _value(stats, group, name, **labels):
    for entry in stats.snapshot()[group].get(name, []):
        if entry["labels"] == labels:
            return entry["value"]
    return 0


def test_stats_count_steps_and_compiles(served):
    server, params = served
    compiles = lambda: _value(server.stats, "counters", "serve.compiles", fn="prefill",
                              stage="compile")
    batch = lambda n: {"inputs": jnp.zeros((B, n), jnp.int32)}
    before = compiles()
    for _ in range(2):
        logits, cache = server.prefill(params, batch(S))
    assert _value(server.stats, "counters", "serve.steps", kind="prefill") == 2
    assert compiles() == before + 1
    assert _value(server.stats, "counters", "serve.compiles", fn="prefill", stage="trace") >= 1
    server.prefill(params, batch(S - 8))  # a new shape compiles again
    assert compiles() == before + 2
    tok = jnp.zeros((B, 1), jnp.int32)
    server.decode(params, cache, tok, S)
    assert _value(server.stats, "counters", "serve.steps", kind="decode") == 1
    assert _value(server.stats, "counters", "serve.compiles", fn="decode_step",
                  stage="compile") == 1


def test_a_compile_counts_once_after_servers_are_dropped(monkeypatch):
    monkeypatch.setattr(serve, "_compile_counts", weakref.WeakSet())  # as if all were dropped
    server = _server("chunked")
    params = server.model.init_params(jax.random.PRNGKey(0))
    server.prefill(params, {"inputs": jnp.zeros((1, 16), jnp.int32)})
    assert _value(server.stats, "counters", "serve.compiles", fn="prefill", stage="compile") == 1


def test_steps_still_lower_and_compile(served):
    server, params = served
    out = server.compile(params, {"inputs": jnp.zeros((B, S), jnp.int32)})
    assert set(out) == {"prefill", "decode"}
    temp = out["decode"][0].memory_analysis().temp_size_in_bytes
    assert "serve.decode_temp_bytes" in server.stats.snapshot()["gauges"]
    assert _value(server.stats, "gauges", "serve.decode_temp_bytes") == temp
    assert "kv_write" in server.prefill.lower(params, {"inputs": jnp.zeros((B, S), jnp.int32)}
                                              ).as_text(debug_info=True)


def test_plan_records_trace_and_walk_seconds(served):
    server, _ = served
    plan = server.plan(B)
    assert plan.records
    trace_s = _value(server.stats, "gauges", "serve.plan_trace_s")
    walk_s = _value(server.stats, "gauges", "serve.plan_walk_s")
    assert trace_s > 0 and walk_s > 0


def test_server_spans_reach_a_profiler_trace(served, tmp_path):
    server, params = served
    with jax.profiler.trace(str(tmp_path)):
        logits, cache = server.prefill(params, {"inputs": jnp.zeros((B, S), jnp.int32)})
        logits, cache = server.decode(params, cache, jnp.zeros((B, 1), jnp.int32), S)
        jax.block_until_ready(logits)
    from jax.profiler import ProfileData

    names = defaultdict(int)
    for plane in ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb")))).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    names[e.name] += 1
    assert dict(names) == {"serve.prefill": 1, "serve.decode": 1}
