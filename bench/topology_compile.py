#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, with no chip attached,
and print what each would hold in device memory.

    JAX_PLATFORMS=cpu python3 bench/topology_compile.py qwen1.5-4b.decode ...

For each cell: the served prefill and decode steps (Pallas kernels in, not
interpreted), the jitted weight draw, and one layer of the reference at the
cell's sample size.  Nothing runs: this shows that the chip's compiler takes
the programs and that each fits, not how fast they are.  Needs the TPU
compiler (libtpu) installed; runs in one process, which holds its lock.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _gib(m) -> str:
    used = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes)
    return (f"arguments {m.argument_size_in_bytes} output {m.output_size_in_bytes} "
            f"temp {m.temp_size_in_bytes} aliased {m.alias_size_in_bytes} "
            f"-> {used} bytes ({used / 1e9:.3f} GB)")


def main(workloads) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, weights
    from bench.reference import family
    from repro.kernels import ops
    from repro.launch.serve import Server

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    place = lambda tree: jax.tree.map(lambda x: spec(x.shape, x.dtype), tree)
    ops._interpret = lambda: False  # the backend here is the CPU: force the kernels
    for name in workloads:
        cell = harness.load_cell(name)
        mix, m = cell.mix, cell.model
        cfg = harness.program_config(cell.config).replace(attn_impl="pallas")
        server = Server(cfg, max_len=mix.prompt_len + mix.output_len)
        params = place(server.model.abstract_params())
        cache = place(server.model.abstract_cache(mix.batch, server.max_len))
        toks = spec((mix.batch, mix.prompt_len), jnp.int32)
        out = {
            "prefill": server.prefill.lower(params, {"inputs": toks}).compile(),
            "decode": server.decode.lower(params, cache, spec((mix.batch, 1), jnp.int32),
                                          spec((), jnp.int32)).compile(),
        }
        rows = server.model.abstract_params()["embed"].shape[0]
        out["weights"] = weights._params.lower(
            weights._freeze(m), spec((2,), jnp.uint32), rows, jnp.dtype(m["dtype"])).compile()
        block = int(cell.check.get("reference_rows", 1))
        T = mix.prompt_len + mix.output_len - 1
        n = int(cell.check["sample_requests"])
        lw = place(jax.eval_shape(lambda k: weights._layer_body(
            weights._freeze(m), k, jnp.uint32(0), jnp.dtype(m["dtype"])), spec((2,), jnp.uint32)))
        out["reference layer"] = family(m)._layer.lower(
            weights._freeze(m), None, block, spec((n, T, m["d_model"]), jnp.float32), lw).compile()
        for step, c in out.items():
            kernels = "tpu_custom_call" in c.as_text()
            print(f"{name} {step}: {_gib(c.memory_analysis())}"
                  f"{' (Pallas kernels in)' if kernels else ''}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
