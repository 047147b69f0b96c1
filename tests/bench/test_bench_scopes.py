"""The program's scopes and spans read from a trace (bench/scope_trace.py):
device time per scope over a program's runs, the per-step times of layers
that the scoped metric readers declare, idle gaps named by the ``Server``'s
spans, an ``.xplane.pb`` read with its ops' metadata, and the committed
excerpts."""

import pytest

import tinycell  # noqa: F401
from bench import scope_trace as st
from bench.trace_reduce import Trace, _family as tr_family

DEV = "/device:TPU:0"
# window 100..300 ns: two decode steps (110..150, 160..200), one prefill
# (210..290) and a step that runs past the window's end
TRACE = st.ScopedTrace(
    window=(100.0, 300.0),
    ops={DEV: [
        ["while.1", 110.0, 40.0, ""],  # the layer scan: its body's ops are listed
        ["fusion.1", 110.0, 4.0, ""],
        ["dynamic-slice_bitcast_fusion.2", 114.0, 6.0, ""],
        ["dynamic_update_slice.3", 120.0, 2.0, ""],
        ["decode_attention.4", 122.0, 8.0, "decode_attention"],
        ["fusion.5", 130.0, 10.0, ""],
        ["copy.6", 140.0, 10.0, ""],
        ["fusion.1", 160.0, 4.0, ""],
        ["dynamic-slice_bitcast_fusion.2", 164.0, 6.0, ""],
        ["dynamic_update_slice.3", 170.0, 2.0, ""],
        ["decode_attention.4", 172.0, 8.0, "decode_attention"],
        ["fusion.5", 180.0, 10.0, ""],
        ["copy.6", 190.0, 10.0, ""],
        ["fusion.7", 210.0, 30.0, ""],
        ["copy.8", 240.0, 10.0, ""],
        ["flash_fwd.9", 250.0, 40.0, "flash_fwd"],
        ["fusion.1", 295.0, 10.0, ""],
    ]},
    modules={DEV: [["jit_decode_step(1)", 110.0, 40.0], ["jit_decode_step(1)", 160.0, 40.0],
                   ["jit_prefill(2)", 210.0, 80.0], ["jit_decode_step(1)", 295.0, 10.0]]},
    host=[["bench.traced", 100.0, 200.0], ["bench.dispatch", 150.0, 10.0],
          ["bench.readback", 200.0, 10.0], ["bench.prepare", 290.0, 10.0]],
    scopes={DEV: ["layers", "qkv", "layers", "kv_write", "attn_core", "ffn", "",
                  "qkv", "layers", "kv_write", "attn_core", "ffn", "",
                  "ffn", "kv_layout", "attn_core", "embed"]},
    program=[["serve.decode", 150.0, 9.0], ["serve.prefill", 205.0, 3.0]],
)


def test_scope_ns_sums_ops_inside_whole_runs():
    # the loop left out, the run past the window left out
    assert TRACE.scope_ns("jit_decode_step") == (2, {
        "qkv": 8.0, "layers": 12.0, "kv_write": 4.0, "attn_core": 16.0, "ffn": 20.0, "": 20.0})
    assert TRACE.scope_ns("jit_prefill") == (1, {"ffn": 30.0, "kv_layout": 10.0,
                                                 "attn_core": 40.0})
    runs, ns = TRACE.scope_ns("jit_decode_step")
    assert sum(ns.values()) == pytest.approx(2 * 40.0)  # the buckets add up to the runs


STEP = st.step_metrics()


def step_ms(trace, name):
    return st.scope_ms(trace, *STEP[name])


def test_step_ms_reads_the_layers():
    assert STEP == {
        "decode_kv_ms": ("jit_decode_step", ("kv_write", "kv_layout")),
        "decode_weights_ms": ("jit_decode_step", ("layers",)),
        "decode_linear_ms": ("jit_decode_step", ("embed", "qkv", "attn_out", "ffn", "logits")),
        "prefill_attn_ms": ("jit_prefill", ("attn_core", "kv_layout")),
    }
    assert step_ms(TRACE, "decode_kv_ms") == pytest.approx(4 / 2 * 1e-6)
    assert step_ms(TRACE, "decode_weights_ms") == pytest.approx(12 / 2 * 1e-6)
    assert step_ms(TRACE, "decode_linear_ms") == pytest.approx((8 + 20) / 2 * 1e-6)
    assert step_ms(TRACE, "prefill_attn_ms") == pytest.approx((40 + 10) * 1e-6)
    bare = st.ScopedTrace(TRACE.window, TRACE.ops, TRACE.modules, TRACE.host)
    assert bare.scope_ns("jit_decode_step") == (2, {"": 80.0})
    assert all(step_ms(bare, name) is None for name in STEP)  # a program without scopes
    none = st.ScopedTrace(TRACE.window, TRACE.ops, {DEV: []}, TRACE.host, TRACE.scopes)
    assert step_ms(none, "decode_kv_ms") is None


@pytest.mark.parametrize("name", sorted(STEP))
def test_scoped_readers_read_through_scope_ms(name):
    """Each reader returns its step time from the run's trace, and None,
    without failing, on a program without scopes and on an untraced run."""
    from bench import harness

    cell = harness.load_cell("qwen1.5-4b.decode")
    run = lambda trace: harness.Run(cell, harness.Window(0.0, 1.0, [], [1]), 1.0, 1.0, {}, trace)
    assert harness.read_metric(name, run(TRACE)) == step_ms(TRACE, name)
    bare = st.ScopedTrace(TRACE.window, TRACE.ops, TRACE.modules, TRACE.host)
    assert harness.read_metric(name, run(bare)) is None
    assert harness.read_metric(name, run(None)) is None


def test_idle_gaps_named_by_the_program():
    # gaps 100..110 (no span), 150..160 (bench.dispatch over all of it and
    # serve.decode inside it over 9 ns), 200..210 (bench.readback over all of
    # it, serve.prefill over 3 ns), 290..295 (bench.prepare)
    ns = pytest.approx(10e-9)
    program = st.ScopedTrace.from_json(TRACE.to_json())
    program.program[0] = ["serve.decode", 150.0, 10.0]
    want = [["host", ns], ["serve.decode", ns], ["readback", ns], ["prepare", pytest.approx(5e-9)]]
    assert program.program_gaps() == want
    assert TRACE.program_gaps() == [want[0], ["dispatch", ns]] + want[2:]
    bare = st.ScopedTrace(TRACE.window, TRACE.ops, TRACE.modules, TRACE.host)
    assert bare.program_gaps() == bare.idle_gaps() == TRACE.program_gaps()


@pytest.mark.parametrize("path, want", [
    ("jit(decode_step)/layers/while/body/closed_call/kv_write/dynamic_update_slice", "kv_write"),
    ("jit(decode_step)/layers/while/body/closed_call/attn_core/jit(decode_attention)/kv_layout/"
     "transpose", "kv_layout"),
    ("jit(decode_step)/layers/while/body/dynamic_slice", "layers"),
    ("jit(prefill)/jit(_take)/gather", ""),
    ("", ""),
])
def test_scope_of_takes_the_innermost(path, want):
    assert st.scope_of(path, st.scope_names()) == want


def test_round_trip_keeps_scopes_and_spans():
    again = st.ScopedTrace.from_json(TRACE.to_json())
    assert again.scope_ns("jit_decode_step") == TRACE.scope_ns("jit_decode_step")
    assert again.program_gaps() == TRACE.program_gaps()
    assert Trace.from_json(TRACE.to_json()).idle_gaps() == TRACE.idle_gaps()


def _xspace(path):
    """An .xplane.pb with a host plane (the window and one program span) and
    a TPU plane (one decode step of three ops, their op_name in the ops'
    metadata as ``tf_op``, one by reference)."""
    space = st._messages()()
    host = space.planes.add(name="/host:CPU")
    for key, name in enumerate(["bench.traced", "serve.decode", "other"], 1):
        host.event_metadata.add(key=key).value.name = name
    line = host.lines.add(name="python", timestamp_ns=1000)
    for key, off, dur in [(1, 0, 1000), (2, 100, 50), (3, 120, 10)]:
        line.events.add(metadata_id=key, offset_ps=off * 1000, duration_ps=dur * 1000)
    tpu = space.planes.add(name=DEV)
    tpu.stat_metadata.add(key=1).value.name = "tf_op"
    tpu.stat_metadata.add(key=2).value.name = "jit(decode_step)/layers/while/body/dynamic_slice:"
    ops = [("%copy.1 = bf16[8] copy(%p)", "jit(decode_step)/layers/while/body/closed_call/"
            "kv_write/dynamic_update_slice:"), ("%fusion.2 = bf16[8] fusion(%copy.1)", None),
           ("%dynamic-slice.3 = bf16[8] dynamic-slice(%p)", 2)]
    for key, (name, op_name) in enumerate(ops, 1):
        md = tpu.event_metadata.add(key=key).value
        md.name = name
        if isinstance(op_name, str):
            md.stats.add(metadata_id=1, str_value=op_name)
        elif op_name is not None:
            md.stats.add(metadata_id=1, ref_value=op_name)
    tpu.event_metadata.add(key=9).value.name = "jit_decode_step(7)"
    mods = tpu.lines.add(name="XLA Modules", timestamp_ns=1000)
    mods.events.add(metadata_id=9, offset_ps=200_000, duration_ps=300_000)
    line = tpu.lines.add(name="XLA Ops", timestamp_ns=1000)
    for key, (off, dur) in enumerate([(200, 100), (300, 150), (450, 50)], 1):
        line.events.add(metadata_id=key, offset_ps=off * 1000 + 400, duration_ps=dur * 1000)
    line.events.add(metadata_id=1, offset_ps=2_000_000, duration_ps=1000)  # past the window
    path.write_bytes(space.SerializeToString())
    return path


def test_load_reads_each_ops_scope_and_the_programs_spans(tmp_path):
    t = st.load(_xspace(tmp_path / "t.xplane.pb"))
    assert t.window == (1000, 2000)
    assert [op[0] for op in t.ops[DEV]] == ["copy.1", "fusion.2", "dynamic-slice.3"]
    assert t.scopes[DEV] == ["kv_write", "", "layers"]
    assert t.program == [["serve.decode", 1100, 50]]
    assert t.scope_ns("jit_decode_step") == (1, {"kv_write": 100, "": 150, "layers": 50})


# -- the committed excerpts --------------------------------------------------------
DATA = tinycell.REPO / "tests" / "bench" / "data"


def test_unscoped_excerpt_reads_as_before():
    """The unscoped prefill excerpt (no scopes, no program spans) loads and every
    reduction reads as ``trace_reduce.Trace`` reads it."""
    text = (DATA / "qwen1.5-4b.prefill.excerpt.json").read_text()
    old, new = Trace.from_json(text), st.ScopedTrace.from_json(text)
    dev = "/device:TPU:0"
    assert new.busy_ns(dev) == old.busy_ns(dev) and new.window_ns == old.window_ns
    for prog in ("jit_prefill", "jit_decode_step"):
        assert new.module_runs(prog) == old.module_runs(prog)
        runs, ns = new.scope_ns(prog)
        assert runs == 1 and set(ns) == {""}
    for kernel in ("flash_fwd", "decode_attention"):
        assert new.kernel_runs(kernel) == old.kernel_runs(kernel)
    assert new.top_ops() == old.top_ops() and new.idle_gaps() == old.idle_gaps()
    assert new.program_gaps() == old.idle_gaps()
    assert all(step_ms(new, name) is None for name in STEP)


def test_scoped_decode_excerpt_reads_as_the_chip_run():
    """Three decode steps of a traced qwen1.5-4b.decode run on a TPU v5e
    (seed 2718281801), reduced by ``scope_trace.load`` and cut to the
    steps and 0.2 ms either side.  The run printed, over its 71 steps,
    13.4954 ms in the cache's scopes and the layer scan's own ops together
    (``kv_write``, ``kv_layout``, ``layers``; that program sliced the cache
    in the scan) and decode_linear_ms 8.1706; of each step 26.86% of the
    device time is in no scope: two copies of the whole stacked cache that
    XLA puts after the layer scan, with no metadata."""
    t = st.ScopedTrace.from_json((DATA / "qwen1.5-4b.decode.scoped.excerpt.json").read_text())
    runs, ns = t.scope_ns("jit_decode_step")
    assert runs == 3 and t.module_runs("jit_decode_step") == (3, pytest.approx(113.69108e6))
    assert sum(ns.values()) == pytest.approx(113.69108e6, rel=1e-3)  # buckets cover the runs
    kv, weights = step_ms(t, "decode_kv_ms"), step_ms(t, "decode_weights_ms")
    assert (kv, weights) == (pytest.approx(0.203236, rel=1e-5), pytest.approx(13.474893, rel=1e-6))
    assert step_ms(t, "decode_linear_ms") == pytest.approx(8.28154, rel=1e-6)
    assert kv + weights == pytest.approx(13.4954, rel=0.02)
    assert step_ms(t, "decode_linear_ms") == pytest.approx(8.1706, rel=0.02)
    assert step_ms(t, "prefill_attn_ms") is None
    assert 100 * ns[""] / sum(ns.values()) == pytest.approx(26.86, abs=1.0)
    unscoped = {tr_family(op[0]) for run in t.runs("jit_decode_step") for op, s in run
                if not s and op[2] > 1e6}
    assert unscoped == {"copy"}
    assert len(t.program) >= 3 and {name for name, _, _ in t.program} == {"serve.decode"}


def test_the_operators_recipe_reads_a_live_servers_spans(tmp_path):
    """README's "Tracing a live `Server`" on the CPU (no device plane):
    the window and the Server's spans come back."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.launch.serve import Server

    server = Server(get_smoke_config("qwen1_5_4b"), max_len=32)
    params = server.model.init_params(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    server.decode(params, server.prefill(params, {"inputs": tokens})[1], tokens[:, :1], 16)
    with jax.profiler.trace(str(tmp_path)), jax.profiler.TraceAnnotation("bench.traced"):
        logits, cache = server.prefill(params, {"inputs": tokens})
        logits, cache = server.decode(params, cache, tokens[:, :1], 16)
        jax.block_until_ready(logits)
    t = st.load(next(tmp_path.rglob("*.xplane.pb")))
    assert [name for name, _, _ in t.program] == ["serve.prefill", "serve.decode"]
    assert st.report(t) == {"step_ms": dict.fromkeys(STEP), "gaps": []}
