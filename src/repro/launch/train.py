"""Training driver: config -> mesh -> sharded params/optimizer -> data
pipeline -> jitted train step -> checkpointed loop with fault-tolerance
hooks.

Usage (CPU-scale example; the same driver lowers onto the production mesh):

  PYTHONPATH=src python -m repro.launch.train --arch chatglm3_6b --smoke \
      --steps 50 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.data import DataPipeline, SyntheticLMSource
from repro.models.common import activate_sharding
from repro.runtime.fault import StragglerDetector

from .compile_cache import setup_compile_cache
from .mesh import data_axes
from .shardings import batch_pspecs, logical_rules, named
from .steps import make_optimizer, make_train_step


class Trainer:
    def __init__(
        self,
        cfg,
        mesh=None,
        global_batch: int = 8,
        seq_len: int = 128,
        ckpt_dir: Optional[str] = None,
        total_steps: int = 1000,
        log_every: int = 10,
    ):
        self.cfg = cfg
        self.mesh = mesh
        self.shape = ShapeConfig("train", "train", seq_len, global_batch)
        self.model, self.opt, self.step_fn = make_train_step(
            cfg, mesh, make_optimizer(total_steps)
        )
        self.ckpt = CheckpointManager(ckpt_dir, keep=3) if ckpt_dir else None
        self.log_every = log_every
        self.stragglers = StragglerDetector()
        self.rules = logical_rules(cfg, self.shape, mesh) if mesh else {}

    # -- state --------------------------------------------------------------

    def init_state(self, seed: int = 0):
        params = self.model.init_params(jax.random.PRNGKey(seed))
        opt_state = self.opt.init(params)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            psh = named(self.mesh, self.model.param_pspecs(self.rules))
            params = jax.device_put(params, psh)
            opt_state = jax.device_put(
                opt_state,
                {"mu": psh, "nu": psh, "step": NamedSharding(self.mesh, P())},
            )
        return params, opt_state

    def maybe_restore(self, params, opt_state):
        start = 0
        if self.ckpt and self.ckpt.latest_step() is not None:
            abstract = {
                "params": jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params),
                "opt": jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), opt_state),
            }
            start, state = self.ckpt.restore(like=abstract)
            params, opt_state = state["params"], state["opt"]
        return start, params, opt_state

    # -- loop ---------------------------------------------------------------

    def train(self, total_steps: int, seed: int = 0, save_every: int = 100):
        cfg = self.cfg
        params, opt_state = self.init_state(seed)
        start, params, opt_state = self.maybe_restore(params, opt_state)

        source = SyntheticLMSource(
            cfg.vocab_size, self.shape.global_batch, self.shape.seq_len, seed=seed,
            embeds_dim=cfg.d_model if cfg.embeds_input else 0,
            frames=cfg.enc_positions if cfg.family == "encdec" else 0,
            mrope=cfg.rope == "mrope",
        )
        if cfg.family == "encdec":
            source.embeds_dim = cfg.d_model
        pipeline = DataPipeline(source, start_step=start, prefetch=2)

        put = None
        if self.mesh is not None:
            bsh = named(self.mesh, batch_pspecs(cfg, self.shape, self.mesh))
            put = lambda b: jax.device_put(b, bsh)

        losses = []
        jit_step = jax.jit(self.step_fn, donate_argnums=(0, 1))
        try:
            with activate_sharding(self.mesh, self.rules) if self.mesh else _null():
                for step, batch in pipeline:
                    if step >= total_steps:
                        break
                    if put:
                        batch = put(batch)
                    t0 = time.perf_counter()
                    params, opt_state, metrics = jit_step(params, opt_state, batch)
                    loss = float(metrics["loss"])
                    dt = time.perf_counter() - t0
                    self.stragglers.record("self", dt)
                    losses.append(loss)
                    if step % self.log_every == 0:
                        tok_s = self.shape.global_batch * self.shape.seq_len / dt
                        print(f"step {step:5d} loss {loss:.4f} {dt*1e3:7.1f} ms/step {tok_s:,.0f} tok/s", flush=True)
                    if self.ckpt and step and step % save_every == 0:
                        self.ckpt.save(step, {"params": params, "opt": opt_state})
        finally:
            pipeline.close()
            if self.ckpt:
                self.ckpt.wait()
        return params, opt_state, losses


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=100)
    args = ap.parse_args()

    setup_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    trainer = Trainer(
        cfg, mesh=None, global_batch=args.batch, seq_len=args.seq,
        ckpt_dir=args.ckpt_dir, total_steps=args.steps,
    )
    _, _, losses = trainer.train(args.steps, save_every=args.save_every)
    print(f"final loss {losses[-1]:.4f} (from {losses[0]:.4f} over {len(losses)} steps)")


if __name__ == "__main__":
    main()
