"""Serving driver: batched prefill + decode loop with the CAPre access plan
wired in (the plan is printed/exported so operators can see exactly what the
step will touch — the paper's prefetching hints for the tensor store).

Usage (CPU-scale example):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1_5_4b --smoke \
      --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.core.access_plan import build_access_plan
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.steps import concrete_batch, make_decode_step, make_prefill_step


KV_BLOCK = 128  # the cache length is padded to this (the decode kernel's tile)


def pad_cache(cfg, cache: dict, max_len: int) -> dict:
    """Grow seq-dim cache buffers to ``max_len`` (static decode shapes)."""
    if cfg.family in ("dense", "moe", "encdec"):
        pad = max_len - cache["k"].shape[2]
        if pad > 0:
            for key in ("k", "v"):
                cache[key] = jnp.pad(cache[key], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    return cache


class Server:
    """Serves ``cfg`` with weights held in the compute dtype (bf16 for the
    published configs: no per-step cast copies of the weights).  On a TPU
    the attention runs the Pallas kernels; the cache length is rounded up
    to a multiple of ``KV_BLOCK`` so the decode kernel can tile it (slots
    past the written prefix are masked by position)."""

    def __init__(self, cfg, mesh=None, max_len: int = 256):
        cfg = cfg.replace(param_dtype=cfg.compute_dtype)
        if jax.default_backend() == "tpu":
            cfg = cfg.replace(attn_impl="pallas")
        self.cfg = cfg
        self.mesh = mesh
        self.max_len = -(-max_len // KV_BLOCK) * KV_BLOCK
        self.model, self.prefill_fn = make_prefill_step(cfg, mesh)
        _, self.decode_fn = make_decode_step(cfg, mesh)

        def prefill(params, batch):
            logits, cache = self.prefill_fn(params, batch)
            return logits, pad_cache(cfg, cache, self.max_len)

        self.prefill = jax.jit(prefill)
        self.decode = jax.jit(self.decode_fn, donate_argnums=(1,))

    def plan(self, batch_size: int):
        """The CAPre access plan of one decode step (compile-time, no
        allocation)."""
        return build_access_plan(
            lambda p, c, t: self.decode_fn(p, c, t, 0),
            self.model.abstract_params(),
            self.model.abstract_cache(batch_size, self.max_len),
            jax.ShapeDtypeStruct((batch_size, 1), jnp.int32),
        )

    def compile(self, params, batch: dict) -> dict:
        """Compile both steps for this batch's shapes ahead of the first
        request; ``generate`` then reuses the executables.  Returns
        ``{step: (compiled, seconds)}``."""
        B, S = batch["inputs"].shape
        args = {
            "prefill": (self.prefill, (params, batch)),
            "decode": (self.decode, (
                params, self.model.abstract_cache(B, self.max_len),
                jax.ShapeDtypeStruct((B, 1), jnp.int32), S,
            )),
        }
        out = {}
        for name, (fn, a) in args.items():
            t0 = time.perf_counter()
            out[name] = (fn.lower(*a).compile(), time.perf_counter() - t0)
        return out

    def generate(self, params, batch: dict, steps: int, greedy: bool = True):
        """Prefill the prompt batch, then decode ``steps`` tokens."""
        B, S = batch["inputs"].shape
        logits, cache = self.prefill(params, batch)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = [tok]
        for i in range(steps - 1):
            logits, cache = self.decode(params, cache, tok, S + i)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(tok)
        return jnp.concatenate(out, axis=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    setup_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    server = Server(cfg, max_len=args.prompt_len + args.gen)
    plan = server.plan(args.batch)
    print(f"access plan: {len(plan.records)} records, "
          f"{len(plan.collections())} collections, {plan.total_bytes/1e6:.1f} MB")
    for h in plan.hints()[:8]:
        print("  hint:", h)

    model = server.model
    params = model.init_params(jax.random.PRNGKey(0))
    batch = concrete_batch(cfg, args.batch, args.prompt_len)
    batch.pop("targets", None)
    t0 = time.perf_counter()
    tokens = server.generate(params, batch, args.gen)
    dt = time.perf_counter() - t0
    print(f"generated {tokens.shape} tokens in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print("sample:", tokens[0, :12].tolist())


if __name__ == "__main__":
    main()
