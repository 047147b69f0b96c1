"""Serving driver: batched prefill + decode loop with the CAPre access plan
wired in (the plan is printed/exported so operators can see exactly what the
step will touch — the paper's prefetching hints for the tensor store).

Usage (CPU-scale example):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1_5_4b --smoke \
      --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import functools
import time
import weakref

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.core.access_plan import plan_from_jaxpr
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.steps import concrete_batch, make_decode_step, make_prefill_step
from repro.models.transformer import kv_major
from repro.obs import Registry, scopes


KV_BLOCK = 128  # the cache length is padded to this (the decode kernel's tile)

# JAX's compile events, by the stage ``serve.compiles`` counts them under
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_compile_counts: "weakref.WeakSet[Registry]" = weakref.WeakSet()  # live Servers' stats


def _on_compile_event(event: str, _secs: float, fun_name: str = "", **_kw) -> None:
    stage = COMPILE_EVENTS.get(event)
    if stage is None:
        return
    # a compile names its program ``jit(prefill)``, a trace the function
    fn = fun_name[4:-1] if fun_name.startswith("jit(") and fun_name.endswith(")") else fun_name
    for stats in list(_compile_counts):
        stats.counter("serve.compiles", fn=fn, stage=stage).inc()


@functools.cache
def _listen() -> None:
    jax.monitoring.register_event_duration_secs_listener(_on_compile_event)


def _count_compiles(stats: Registry) -> None:
    """Count every trace and compile in the process into ``stats`` while it
    lives, from one ``jax.monitoring`` listener shared by all Servers."""
    _listen()
    _compile_counts.add(stats)


def pad_cache(cfg, cache: dict, max_len: int) -> dict:
    """Grow the seq dim of the k/v cache buffers to ``max_len`` (static
    decode shapes)."""
    if cfg.family in ("dense", "moe", "encdec"):
        seq = 3 if kv_major(cfg) else 2  # [L, B, KV, S, hd] or [L, B, S, KV, hd]
        pad = max_len - cache["k"].shape[seq]
        if pad > 0:
            with scopes.scope("kv_write"):
                for key in ("k", "v"):
                    widths = [(0, 0)] * 5
                    widths[seq] = (0, pad)
                    cache[key] = jnp.pad(cache[key], widths)
    return cache


class Step:
    """A jitted step that counts its calls and runs each inside the profiler
    span ``span``: on a trace's host plane, on the device ops' clock, it
    names what the host was doing.  ``lower`` is the jitted function's."""

    def __init__(self, fn, span: str, calls):
        self._fn, self._span, self._calls = fn, span, calls
        self.lower = fn.lower

    def __call__(self, *args):
        self._calls.inc()
        with jax.profiler.TraceAnnotation(self._span):
            return self._fn(*args)


class Server:
    """Serves ``cfg`` with weights held in the compute dtype (bf16 for the
    published configs: no per-step cast copies of the weights).  On a TPU
    the attention runs the Pallas kernels; the cache length is rounded up
    to a multiple of ``KV_BLOCK`` so the decode kernel can tile it (slots
    past the written prefix are masked by position).

    ``stats`` (a ``repro.obs.Registry``): counters ``serve.steps{kind}``
    (calls of ``prefill`` and ``decode``) and ``serve.compiles{fn, stage}``
    (every jaxpr trace and backend compile in the process while the Server
    lives, by function name), gauges ``serve.plan_trace_s`` and
    ``serve.plan_walk_s`` (the last ``plan``: tracing the decode step, and
    walking its jaxpr) and ``serve.decode_temp_bytes`` (the last
    ``compile``: the decode step's scratch memory, which stays far under one
    layer stack of the cache while the step writes the cache in place)."""

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

        self.stats = Registry()
        _count_compiles(self.stats)
        steps = lambda kind: self.stats.counter("serve.steps", kind=kind)
        self.prefill = Step(jax.jit(prefill), "serve.prefill", steps("prefill"))
        self.decode = Step(jax.jit(self.decode_fn, donate_argnums=(1,)), "serve.decode",
                           steps("decode"))

    def plan(self, batch_size: int):
        """The CAPre access plan of one decode step (compile-time, no
        allocation)."""
        params = self.model.abstract_params()
        t0 = time.perf_counter()
        closed = jax.make_jaxpr(lambda p, c, t: self.decode_fn(p, c, t, 0))(
            params, self.model.abstract_cache(batch_size, self.max_len),
            jax.ShapeDtypeStruct((batch_size, 1), jnp.int32))
        t1 = time.perf_counter()
        plan = plan_from_jaxpr(closed, params)
        self.stats.gauge("serve.plan_trace_s").set(t1 - t0)
        self.stats.gauge("serve.plan_walk_s").set(time.perf_counter() - t1)
        return plan

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
        memory = out["decode"][0].memory_analysis()
        if memory is not None:
            self.stats.gauge("serve.decode_temp_bytes").set(memory.temp_size_in_bytes)
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
