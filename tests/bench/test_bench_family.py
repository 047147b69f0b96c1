"""A model family is one module under bench/reference/, and the shared
harness takes a new one with new files only.

Dense is held to what it was: the program check still refuses each field
that differs.  Then a family that no file under bench/ knows, defined here
with a leaf dense lacks (``attn.q_norm``), banks of experts ``[E, d, f]``
and a tied head, goes through the shared code: its weights are drawn, the
program's smoke configuration is held to its fields, its work counts reach
a metric reader, and a reader that declares a kernel gets that kernel
counted in a trace."""

import json
import sys
import types

import jax
import numpy as np
import pytest

import tinycell
from bench import harness, weights
from bench import scope_trace as st
from bench.reference import dense, family
from repro.configs import get_smoke_config
from repro.models.model import Model

QWEN = json.loads((tinycell.REPO / "bench" / "configs" / "qwen1.5-4b.json").read_text())


def _other(value):
    if isinstance(value, bool):
        return not value
    if isinstance(value, (int, float)):
        return value + 1
    return value + "_other"


@pytest.mark.parametrize("field", sorted(dense.program_fields(harness.model_block(QWEN))))
def test_program_config_refuses_each_mismatched_field(field, monkeypatch):
    import repro.configs

    assert harness.program_config(QWEN).name == "qwen1.5-4b"
    real = repro.configs.get_config
    wrong = lambda name: real(name).replace(**{field: _other(getattr(real(name), field))})
    monkeypatch.setattr(repro.configs, "get_config", wrong)
    with pytest.raises(ValueError, match=field):
        harness.program_config(QWEN)


# -- a family defined only here -------------------------------------------------

NAME = "plug_moe"  # no bench/reference/plug_moe.py


def _layer_shapes(m):
    d, f, E, hd = m["d_model"], m["d_ff"], m["n_experts"], m["head_dim"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    return {"ln1": (d,), "ln2": (d,), "attn.wq": (d, q), "attn.wk": (d, kv), "attn.wv": (d, kv),
            "attn.wo": (q, d), "attn.q_norm": (hd,), "attn.k_norm": (hd,), "mlp.router": (d, E),
            "mlp.we_gate": (E, d, f), "mlp.we_up": (E, d, f), "mlp.we_down": (E, f, d)}


def _top_shapes(m, rows):
    d = m["d_model"]
    return {"embed": (rows, d), "final_norm": (d,),
            **({} if m["tie_embeddings"] else {"lm_head": (d, rows)})}


def _attn_params(m):
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return 2 * m["d_model"] * (q + kv)


def _expert_params(m):
    return 3 * m["d_model"] * m["d_ff"]


def _module():
    mod = types.ModuleType(f"bench.reference.{NAME}")
    mod.LAYER = (("ln1", "around_one", 0.1), ("ln2", "around_one", 0.1),
                 ("attn.wq", "normal", None), ("attn.wk", "normal", None),
                 ("attn.wv", "normal", None), ("attn.wo", "normal", None),
                 ("attn.q_norm", "around_one", 0.1), ("attn.k_norm", "around_one", 0.1),
                 ("mlp.router", "normal", None), ("mlp.we_gate", "normal", None),
                 ("mlp.we_up", "normal", None), ("mlp.we_down", "normal", None))
    mod.TOP = (("embed", "normal", 1.0), ("lm_head", "normal", None),
               ("final_norm", "around_one", 0.1))
    mod.layer_shapes, mod.top_shapes = _layer_shapes, _top_shapes
    mod.program_fields = lambda m: {
        "family": "moe", "qk_norm": True, "tie_embeddings": m["tie_embeddings"],
        **{k: m[k] for k in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
                             "d_ff", "vocab_size", "n_experts", "experts_per_token")}}
    # a token passes through the router and its k experts
    mod.layer_matmul_params = lambda m: (_attn_params(m) + m["d_model"] * m["n_experts"]
                                         + m["experts_per_token"] * _expert_params(m))
    mod.layer_params = lambda m: (_attn_params(m) + m["d_model"] * m["n_experts"]
                                  + m["n_experts"] * _expert_params(m))
    # at least k experts a layer, however the batch routes
    mod.decode_weight_bytes = lambda m, batch: 2 * (
        m["n_layers"] * (_attn_params(m) + m["d_model"] * m["n_experts"]
                         + m["experts_per_token"] * _expert_params(m))
        + m["d_model"] * m["vocab_size"] + batch * m["d_model"])
    return mod


# the program's qwen3-moe smoke preset
SPEC = {"program_config": "qwen3_moe_30b_a3b", "reference": NAME, "model": {
    "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 32,
    "vocab_size": 128, "n_experts": 8, "experts_per_token": 2, "tie_embeddings": False,
    "dtype": "bfloat16"}}
TIED = {**harness.model_block(SPEC), "tie_embeddings": True}


@pytest.fixture(autouse=True)
def plugged(monkeypatch):
    monkeypatch.setitem(sys.modules, f"bench.reference.{NAME}", _module())


def test_its_weights_are_drawn():
    key = weights.root_key(2**35 + 1)
    tree = weights.make_params(TIED, key, 256, "bfloat16")
    program = Model(get_smoke_config("qwen3_moe_30b_a3b").replace(tie_embeddings=True))
    shapes = lambda t: jax.tree.map(lambda x: x.shape, t)
    assert shapes(tree) == shapes(program.abstract_params())  # no lm_head: tied
    bank = np.asarray(tree["layers"]["mlp"]["we_gate"], np.float32)  # [L, E, d, f]
    assert bank.std() == pytest.approx(64 ** -0.5, rel=0.05)  # fan-in d, not E
    norm = np.asarray(tree["layers"]["attn"]["q_norm"], np.float32)
    assert norm.mean() == pytest.approx(1.0, abs=0.05) and norm.std() > 0.05
    one = weights.layer(TIED, key, 1, "bfloat16")
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a[1], b), tree["layers"], one)


def test_the_program_is_held_to_its_fields():
    smoke = {**SPEC, "program_preset": "smoke"}
    assert harness.program_config(smoke).family == "moe"
    wrong = {**smoke, "model": {**SPEC["model"], "n_experts": 16}}
    with pytest.raises(ValueError, match="n_experts"):
        harness.program_config(wrong)


DEV = "/device:TPU:0"


def _trace(kernel_family):
    """Two decode steps of 50 ns, each with two calls of a kernel."""
    ops = [[f"{kernel_family}.{i}", 10.0 + 50 * s + 20 * i, 10.0, kernel_family]
           for s in range(2) for i in range(2)]
    return st.ScopedTrace((0.0, 200.0), {DEV: ops},
                          {DEV: [["jit_decode_step(3)", 10.0 + 50 * s, 50.0] for s in range(2)]},
                          [["bench.traced", 0.0, 200.0]], {DEV: [""] * len(ops)})


def test_its_counts_reach_the_readers(tmp_path):
    root = tinycell.make_root(tmp_path, configs={"tiny-plug": SPEC})
    cell = harness.load_cell("tiny-plug.tiny_decode", root)
    assert family(cell.model).__name__ == f"bench.reference.{NAME}"
    m, B, L = cell.model, cell.mix.batch, 30
    window = harness.Window(0.0, 1.0, [], decode_kv_lens=[L])
    run = harness.Run(cell, window, 1.0, 1.0, {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
                      _trace("none"))
    d, H, hd = m["d_model"], m["n_heads"], m["head_dim"]
    active = 2 * d * (64 + 32) + d * 8 + 2 * 3 * d * 32  # attention, router, 2 experts
    step = m["n_layers"] * (2 * B * active + 4 * B * H * hd * L) + 2 * B * d * m["vocab_size"]
    assert harness.read_metric("mfu.decode", run) == pytest.approx(
        100 * 2 * step / 100e-9 / 1e12)
    read = 2 * (m["n_layers"] * active + d * m["vocab_size"] + B * d)
    kv = 2 * m["n_layers"] * m["n_kv_heads"] * hd * 2 * B * L
    least = max(step / 1e12, (read + kv) / 1e11)
    assert harness.read_metric("decode_step_roofline", run) == pytest.approx(
        100 * 2 * least / 100e-9)


READER = '''"""Calls of a kernel that no shared file names."""

KERNEL = "expert_ffn"


def read(run):
    n, ns = run.trace.kernel_runs(KERNEL)
    return n or None
'''


def test_a_declared_kernel_is_counted(tmp_path):
    root = tinycell.make_root(tmp_path, configs={"tiny-plug": SPEC})
    (root / "bench" / "metrics" / "expert_ffn_calls.py").write_text(READER)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["per_layer"].append({"name": "expert_ffn_calls", "unit": "calls", "better": "lower",
                                  "source": "device_trace", "layer": "experts",
                                  "moves": "tpot_p95_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    cell = harness.load_cell("tiny-plug.tiny_decode", root)
    assert "expert_ffn" in harness.kernels(cell)
    trace = _trace("expert_ffn")
    path = tmp_path / "t.xplane.pb"
    _write_xspace(path, trace)
    loaded = harness.load_trace(path, cell)
    assert loaded.kernel_runs("expert_ffn") == (4, 40.0)
    run = harness.Run(cell, harness.Window(0.0, 1.0, [], [30]), 1.0, 1.0, {}, loaded)
    assert harness.read_metric("expert_ffn_calls", run) == 4
    plain = harness.Cell(cell.name, cell.chips, cell.config, cell.mix, cell.check,
                         cell.end_to_end, [], root)
    assert harness.load_trace(path, plain).kernel_runs("expert_ffn") == (0, 0.0)


def _write_xspace(path, trace):
    """An .xplane.pb holding the trace's window span, ops and programs."""
    space = st._messages()()
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "bench.traced"
    line = host.lines.add(name="python", timestamp_ns=0)
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=int(trace.window[1]) * 1000)
    tpu = space.planes.add(name=DEV)
    ops, mods = tpu.lines.add(name="XLA Ops"), tpu.lines.add(name="XLA Modules")
    for key, (name, start, dur) in enumerate(
            [(op[0], op[1], op[2]) for op in trace.ops[DEV]], 1):
        tpu.event_metadata.add(key=key).value.name = name
        ops.events.add(metadata_id=key, offset_ps=int(start) * 1000, duration_ps=int(dur) * 1000)
    for key, (name, start, dur) in enumerate(trace.modules[DEV], 100):
        tpu.event_metadata.add(key=key).value.name = name
        mods.events.add(metadata_id=key, offset_ps=int(start) * 1000, duration_ps=int(dur) * 1000)
    path.write_bytes(space.SerializeToString())
