"""The reduction from a trace to metrics (bench/trace_reduce.py) on a small
trace made by hand: busy time as a union of op intervals clipped to the
window, programs and kernels counted only when wholly inside it, idle gaps
named by the host span over them."""

import pytest

import tinycell  # noqa: F401
from bench.trace_reduce import Trace

DEV = "/device:TPU:0"
# window 100..200 ns; ops overlap at 120..130 and one runs past the end
TRACE = Trace(
    window=(100.0, 200.0),
    ops={DEV: [
        ["fusion.1", 90.0, 20.0, ""],  # 100..110 inside
        ["decode_attention.3", 120.0, 10.0, "decode_attention"],
        ["fusion.2", 125.0, 10.0, ""],  # overlaps: union 120..135
        ["decode_attention.4", 150.0, 20.0, "decode_attention"],
        ["fusion.7", 190.0, 30.0, ""],  # 190..200 inside
        ["while.2", 100.0, 100.0, ""],  # a loop: its body ops are the others
    ]},
    modules={DEV: [["jit_decode_step(1)", 115.0, 60.0], ["jit_decode_step(2)", 180.0, 40.0],
                   ["jit_prefill(3)", 90.0, 5.0]]},
    host=[["bench.traced", 100.0, 100.0], ["bench.dispatch", 100.0, 12.0],
          ["bench.readback", 135.0, 15.0], ["bench.prepare", 170.0, 30.0]],
)


def test_busy_is_the_union_inside_the_window():
    # 100..110, 120..135, 150..170, 190..200
    without_loop = Trace(TRACE.window, {DEV: TRACE.ops[DEV][:-1]}, TRACE.modules, TRACE.host)
    assert without_loop.busy_ns(DEV) == pytest.approx(10 + 15 + 20 + 10)
    assert without_loop.mean_busy_ns() == pytest.approx(55)
    assert TRACE.busy_ns(DEV) == pytest.approx(100)  # the loop covers it all
    assert TRACE.window_ns == 100


def test_programs_and_kernels_wholly_inside():
    assert TRACE.module_runs("jit_decode_step") == (1, 60.0)
    assert TRACE.module_runs("jit_prefill") == (0, 0.0)
    assert TRACE.kernel_runs("decode_attention") == (2, 30.0)
    assert TRACE.kernel_runs("flash_fwd") == (0, 0.0)


def test_idle_gaps_named_by_the_host():
    # gaps: 110..120 (dispatch covers 2 ns of it; nothing else), 135..150
    # (readback), 170..190 (prepare)
    gaps = Trace(TRACE.window, {DEV: TRACE.ops[DEV][:-1]}, TRACE.modules, TRACE.host).idle_gaps()
    assert gaps == [["prepare", pytest.approx(20e-9)], ["readback", pytest.approx(15e-9)],
                    ["dispatch", pytest.approx(10e-9)]]


def test_top_ops_group_numbered_names():
    top = dict(TRACE.top_ops())
    assert "while" not in top
    assert top["decode_attention"] == pytest.approx(30e-9)
    assert top["fusion"] == pytest.approx(10e-9 + 10e-9 + 10e-9)


def test_round_trip():
    again = Trace.from_json(TRACE.to_json())
    assert again.busy_ns(DEV) == TRACE.busy_ns(DEV)
    assert again.idle_gaps() == TRACE.idle_gaps()


# -- a real trace ----------------------------------------------------------------
# One batch of the qwen1.5-4b.prefill cell on a TPU v5e, reduced by
# trace_reduce.load and cut to one prefill and the decode step after it.
REAL = Trace.from_json((tinycell.REPO / "tests" / "bench" / "data" /
                        "qwen1.5-4b.prefill.excerpt.json").read_text())


def test_real_trace_reduction():
    dev = "/device:TPU:0"
    assert list(REAL.ops) == [dev]
    assert REAL.module_runs("jit_prefill") == (1, pytest.approx(258.260908e6))
    assert REAL.module_runs("jit_decode_step") == (1, pytest.approx(34.591386e6))
    # one call of each kernel per layer
    n, ns = REAL.kernel_runs("flash_fwd")
    assert n == 40 and ns == pytest.approx(98.60842e6)
    n, ns = REAL.kernel_runs("decode_attention")
    assert n == 40 and ns == pytest.approx(11.375027e6)
    busy = REAL.busy_ns(dev)
    assert ns < busy <= REAL.window_ns
    assert 100 * (1 - busy / REAL.window_ns) == pytest.approx(0.072248, rel=1e-3)


def test_readers_on_the_real_trace():
    from bench import flops, harness

    cell = harness.load_cell("qwen1.5-4b.prefill")
    window = harness.Window(0.0, 30.0, [], decode_kv_lens=[1025] * 7, trace=REAL)
    run = harness.Run(cell, window, 1.0, 1.0, harness.peaks("TPU v5 lite"), REAL)
    mfu = harness.read_metric("mfu.prefill", run)
    want = flops.prefill_flops(cell.model, 4, 1024) / 258.260908e-3 / 197e12 * 100
    assert mfu == pytest.approx(want) and 40 < mfu < 100
    flash = harness.read_metric("flash_fwd_roofline", run)
    f, _ = flops.flash_fwd_call(cell.model, 4, 1024)
    assert flash == pytest.approx(100 * 40 * f / 197e12 / 98.60842e-3) and 0 < flash < 100
    assert harness.read_metric("device_idle", run) == pytest.approx(0.072248, rel=1e-3)
    assert harness.read_metric("device_idle.decode", run) == harness.read_metric("device_idle", run)
