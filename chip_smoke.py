#!/usr/bin/env python3
"""Serve qwen1.5-4b at full published width on one TPU chip, once, and check
what comes out.

    python chip_smoke.py

One process, one chip.  It goes through the entry points a user calls:
``repro.launch.serve.Server`` (weights in bf16, Pallas attention on a TPU),
its CAPre access plan (``build_access_plan``) and ``Model.init_params`` with
weights drawn from a seed.  Phases, each fatal on failure:

  1. set-up: config, access-plan summary, parameter bytes;
  2. compile both served steps and prove the Pallas kernels are in them
     (``tpu_custom_call`` in each executable's text);
  3. serve B=4 prompts of 512 tokens, decode 32 tokens;
  4. agreement on the same chip, each error printed beside its limit:
     - the served logits (prefill and every decode step) against the jnp
       attention path (``attn_impl="chunked"``), teacher-forced on the
       served tokens, beside the gap between the two jnp paths;
     - decode logits at step t against a fresh prefill of prompt + t tokens;
     - ``decode_attention`` and ``flash_attention`` against ``kernels/ref.py``;
  5. peak device memory.

Exits non-zero, without the result line, when JAX finds no TPU or the repro
package is not beside this script.  The last line of stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "qwen1_5_4b"
BATCH, PROMPT, GEN, SEED = 4, 512, 32, 0
REPREFILL_STEPS = (1, 16, GEN - 1)  # decode step t vs a prefill of prompt + t
KV_LEN = 523  # kernel check: a valid length that ends inside a KV block

# Agreement limits, as max|got - want| / max|want|.  bf16 rounds to 8
# significant bits: unit roundoff u = 2**-8.  A kernel's output is one bf16
# rounding of a softmax-weighted sum of bf16 values, so it may differ from
# the oracle by a few u of its largest entry: 2e-2 (5 u, the repo's bf16
# kernel test tolerance).  Two forward paths that round their bf16
# activations differently drift apart at each of the 2 x 40 residual
# updates, like a random walk: about sqrt(80) u = 9 u of the largest logit
# (on a v5e the Pallas and jnp prefills differ by 4.0%, 10 u).  Logits get
# 16 u.  A wrong mask, head mapping or cache slot moves logits by a large
# fraction of their scale.  The two jnp attention paths (naive, chunked)
# are held to the same limit: their gap is the floor this one is read
# against.
KERNEL_LIMIT = 2e-2
LOGIT_LIMIT = 16 * 2.0**-8
HBM_LIMIT = 16e9  # bytes: one v5e chip


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def agreement(name: str, got, want, limit: float) -> bool:
    """Print the error beside its limit; return whether it is within."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        log(f"  {name}: shape {got.shape} vs {want.shape}, or non-finite values FAIL")
        return False
    abs_err = float(np.max(np.abs(got - want)))
    rel_err = abs_err / max(float(np.max(np.abs(want))), 1e-30)
    ok = rel_err <= limit
    log(f"  {name:44s} max_abs={abs_err:.6g} rel={rel_err:.6g} limit={limit:.6g} "
        f"{'ok' if ok else 'FAIL'}")
    return ok


def teacher_force(prefill, decode, params, batch, tokens):
    """Logits [steps, B, V] in f32: the prompt's, then after each of
    ``tokens[:, :-1]`` is fed back."""
    import jax.numpy as jnp

    S = batch["inputs"].shape[1]
    logits, cache = prefill(params, batch)
    out = [logits[:, -1]]
    for i in range(tokens.shape[1] - 1):
        logits, cache = decode(params, cache, tokens[:, i : i + 1], S + i)
        out.append(logits[:, -1])
    return jnp.stack(out).astype(jnp.float32)


def run(cfg) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.launch.serve import Server, pad_cache
    from repro.launch.steps import make_decode_step, make_prefill_step

    # -- 1. set-up -----------------------------------------------------------
    server = Server(cfg, max_len=PROMPT + GEN)
    c = server.cfg
    log(f"config {c.name}: layers={c.n_layers} d_model={c.d_model} heads={c.n_heads} "
        f"kv_heads={c.n_kv_heads} head_dim={c.head_dim} d_ff={c.d_ff} vocab={c.vocab_size} "
        f"param_dtype={c.param_dtype} attn_impl={c.attn_impl} cache_len={server.max_len}")
    t0 = time.perf_counter()
    plan = server.plan(BATCH)
    step_bytes = plan.total_bytes
    log(f"access plan: {len(plan.records)} records, {len(plan.collections())} collections, "
        f"{step_bytes} bytes per decode step ({time.perf_counter() - t0:.3f} s to derive)")
    t0 = time.perf_counter()
    params = jax.block_until_ready(server.model.init_params(jax.random.PRNGKey(SEED)))
    leaves = jax.tree.leaves(params)
    n_params = sum(x.size for x in leaves)
    param_bytes = sum(x.nbytes for x in leaves)
    log(f"params: {n_params} ({param_bytes} bytes, dtypes {sorted({str(x.dtype) for x in leaves})}) "
        f"initialised from seed {SEED} in {time.perf_counter() - t0:.3f} s")
    if {x.dtype for x in leaves} != {jnp.dtype(c.compute_dtype)}:
        raise SmokeFailure("served weights are not held in the compute dtype")
    prompts = jax.random.randint(jax.random.PRNGKey(SEED + 1), (BATCH, PROMPT), 0, c.vocab_size)
    batch = {"inputs": prompts.astype(jnp.int32)}

    # -- 2. compile, and prove the kernels are in both steps -----------------
    for name, (compiled, secs) in server.compile(params, batch).items():
        if "tpu_custom_call" not in compiled.as_text():
            raise SmokeFailure(f"{name}: no tpu_custom_call: the Pallas kernels did not run")
        log(f"compile {name}: {secs:.3f} s, tpu_custom_call present")

    # -- 3. serve --------------------------------------------------------------
    t0 = time.perf_counter()
    tokens = jax.block_until_ready(server.generate(params, batch, GEN))
    log(f"served {BATCH} x {PROMPT}-token prompts, {GEN} tokens each, in "
        f"{time.perf_counter() - t0:.3f} s (host clock, first call)")
    if tokens.shape != (BATCH, GEN) or int(tokens.min()) < 0 or int(tokens.max()) >= c.vocab_size:
        raise SmokeFailure(f"generated tokens: shape {tokens.shape}, range "
                           f"[{int(tokens.min())}, {int(tokens.max())}]")
    log(f"sample tokens: {tokens[0, :12].tolist()}")

    # -- 4. agreement (every comparison printed; the phase fails after) ------
    log("agreement (max|got - want| / max|want|):")
    checks = []

    key = jax.random.split(jax.random.PRNGKey(SEED + 2), 3)
    H, KV, D, L = c.n_heads, c.n_kv_heads, c.head_dim, server.max_len
    dt = jnp.dtype(c.compute_dtype)
    q = jax.random.normal(key[0], (BATCH, H, D), dt)
    k = jax.random.normal(key[1], (BATCH, L, KV, D), dt)
    v = jax.random.normal(key[2], (BATCH, L, KV, D), dt)
    checks.append(agreement(
        f"decode_attention vs ref (kv_len {KV_LEN}/{L})",
        ops.decode_attention(q, k, v, KV_LEN), ref.decode_attention_ref(q, k, v, KV_LEN),
        KERNEL_LIMIT))
    q = jax.random.normal(key[0], (BATCH, PROMPT, H, D), dt)
    k = jax.random.normal(key[1], (BATCH, PROMPT, KV, D), dt)
    v = jax.random.normal(key[2], (BATCH, PROMPT, KV, D), dt)
    checks.append(agreement(
        "flash_attention (causal) vs ref", ops.flash_attention(q, k, v, causal=True),
        ref.flash_attention_ref(q, k, v, causal=True), KERNEL_LIMIT))
    del q, k, v

    served = teacher_force(server.prefill, server.decode, params, batch, tokens)
    if not bool(jnp.all(jnp.argmax(served, -1).T == tokens)):
        raise SmokeFailure("teacher-forced served logits do not reproduce the served tokens")

    def jnp_prefill(impl):
        cfg_j = c.replace(attn_impl=impl)
        _, prefill = make_prefill_step(cfg_j)

        def padded(p, b):
            logits, cache = prefill(p, b)
            return logits, pad_cache(cfg_j, cache, server.max_len)

        return jax.jit(padded)

    _, jnp_decode = make_decode_step(c.replace(attn_impl="chunked"))
    jnp_path = teacher_force(jnp_prefill("chunked"), jax.jit(jnp_decode, donate_argnums=(1,)),
                             params, batch, tokens)
    checks.append(agreement("prefill logits: jnp naive vs jnp chunked",
                            jnp_prefill("naive")(params, batch)[0][:, -1], jnp_path[0],
                            LOGIT_LIMIT))
    checks.append(agreement("prefill logits: pallas vs jnp", served[0], jnp_path[0],
                            LOGIT_LIMIT))
    for t in range(1, GEN):
        checks.append(agreement(f"decode step {t} logits: pallas vs jnp", served[t],
                                jnp_path[t], LOGIT_LIMIT))

    for t in REPREFILL_STEPS:
        longer = {"inputs": jnp.concatenate([batch["inputs"], tokens[:, :t]], axis=1)}
        logits, _ = server.prefill(params, longer)
        checks.append(agreement(f"decode step {t} vs prefill of {PROMPT}+{t}", served[t],
                                logits[:, -1], LOGIT_LIMIT))
    if not all(checks):
        raise SmokeFailure(f"{checks.count(False)} of {len(checks)} agreement checks over limit")

    # -- 5. memory -------------------------------------------------------------
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"peak_bytes_in_use: {peak} (limit {HBM_LIMIT:.0f}; bytes_limit {stats.get('bytes_limit')})")
    if peak is None or peak >= HBM_LIMIT:
        raise SmokeFailure(f"peak device memory {peak} not under {HBM_LIMIT:.0f}")


def main() -> int:
    if not (SRC / "repro" / "launch" / "serve.py").is_file():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import setup_compile_cache

    log(f"compile cache: {setup_compile_cache()}")
    import jax

    from repro.configs import get_config

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    try:
        run(get_config(ARCH))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
