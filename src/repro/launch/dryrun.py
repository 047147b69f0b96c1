"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes (single-pod 16x16 = 256 chips; multi-pod
2x16x16 = 512 chips), proving the distribution config is coherent, and
record the roofline inputs per cell:

  * ``compiled.memory_analysis()``  — proves the step fits per-device HBM
  * loop-aware jaxpr FLOPs/bytes    — launch/costmodel.py (XLA's own
    cost_analysis does not scale while bodies by trip count; we record both)
  * per-device collective bytes     — parsed from the partitioned HLO with
    while-trip multiplication (launch/hlo_parse.py)

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>.json and are
consumed by benchmarks/roofline.py and EXPERIMENTS.md.

The dry run compiles for 512 virtual CPU devices and never takes an
accelerator: ``main()`` pins itself and its ``--all`` children to
``JAX_PLATFORMS=cpu`` before JAX is imported.

Usage:
  python -m repro.launch.dryrun --arch granite_moe_1b_a400m --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--jobs 2]
"""

import argparse
import json
import os
import subprocess
import sys
import time
import traceback
from pathlib import Path

ART_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _abstract_f32(tree):
    import jax

    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, "float32"), tree)


def run_cell(arch: str, shape_name: str, mesh_kind: str, overrides: dict | None = None) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import SHAPES, get_config, runnable_shapes
    from repro.models.common import activate_sharding
    from repro.models.model import Model
    from .costmodel import step_cost
    from .hlo_parse import collective_bytes
    from .mesh import make_production_mesh
    from .shardings import batch_pspecs, cache_pspecs, logical_rules, named
    from .steps import decode_input_specs, input_specs, make_decode_step, make_prefill_step, make_train_step

    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "kind": shape.kind, "status": "running",
        "overrides": dict(overrides or {}),
    }
    if shape not in runnable_shapes(cfg):
        rec["status"] = "skipped"
        rec["reason"] = "long_500k requires sub-quadratic attention (DESIGN.md §Arch-applicability)"
        return rec

    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    rec["chips"] = mesh.size
    rules = logical_rules(cfg, shape, mesh)
    model = Model(cfg)
    params_abs = model.abstract_params()
    params_sh = named(mesh, model.param_pspecs(rules))

    def build_step(c):
        if shape.kind == "train":
            _, _opt, s = make_train_step(c, mesh)
        elif shape.kind == "prefill":
            _, s = make_prefill_step(c, mesh)
        else:
            _, s = make_decode_step(c, mesh)
        return s

    if shape.kind == "train":
        step = build_step(cfg)
        opt_abs = {
            "mu": _abstract_f32(params_abs),
            "nu": _abstract_f32(params_abs),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }
        opt_sh = {
            "mu": params_sh, "nu": params_sh,
            "step": NamedSharding(mesh, P()),
        }
        batch_abs = input_specs(cfg, shape)
        batch_sh = named(mesh, batch_pspecs(cfg, shape, mesh))
        args = (params_abs, opt_abs, batch_abs)
        in_sh = (params_sh, opt_sh, batch_sh)
        out_sh = (params_sh, opt_sh, None)
        donate = (0, 1)
    elif shape.kind == "prefill":
        step = build_step(cfg)
        batch_abs = input_specs(cfg, shape)
        batch_sh = named(mesh, batch_pspecs(cfg, shape, mesh))
        args = (params_abs, batch_abs)
        in_sh = (params_sh, batch_sh)
        out_sh = None
        donate = ()
    else:  # decode
        step = build_step(cfg)
        cache_abs, tok_abs, pos_abs = decode_input_specs(cfg, shape)
        cache_sh = named(mesh, cache_pspecs(cfg, shape, mesh))
        rules_b = logical_rules(cfg, shape, mesh)["batch"]
        tok_sh = NamedSharding(mesh, P(rules_b, None))
        pos_sh = NamedSharding(mesh, P())
        args = (params_abs, cache_abs, tok_abs, pos_abs)
        in_sh = (params_sh, cache_sh, tok_sh, pos_sh)
        out_sh = (None, cache_sh)
        donate = (1,)

    # --- loop-aware jaxpr cost (global totals) ---
    # Pallas kernels can't lower for the CPU SPMD backend, so when the
    # config selects them the COST is derived from the kernel jaxpr (its
    # true HBM traffic/FLOPs) while the COMPILE uses the numerically
    # equivalent chunked lowering — attention adds no collectives, so the
    # collective analysis is unaffected (EXPERIMENTS.md §Perf notes this).
    cost_step = step
    if cfg.attn_impl == "pallas":
        step = build_step(cfg.replace(attn_impl="chunked"))
    t0 = time.perf_counter()
    with activate_sharding(mesh, rules):
        cost = step_cost(cost_step, *args)
    rec["jaxpr_flops"] = cost.flops
    rec["jaxpr_dot_flops"] = cost.dot_flops
    rec["jaxpr_bytes"] = cost.bytes
    rec["jaxpr_collective_bytes"] = cost.collective_bytes
    rec["t_trace_s"] = time.perf_counter() - t0

    # --- lower + compile on the production mesh ---
    t0 = time.perf_counter()
    with activate_sharding(mesh, rules):
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=donate)
        lowered = jitted.lower(*args)
    rec["t_lower_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    rec["t_compile_s"] = time.perf_counter() - t0

    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes",
            ):
                v = getattr(mem, k, None)
                if v is not None:
                    rec[f"mem_{k}"] = int(v)
    except Exception as e:  # pragma: no cover
        rec["mem_error"] = str(e)
    try:
        ca = compiled.cost_analysis()
        if ca:
            rec["xla_flops_unscaled"] = float(ca.get("flops", -1.0))
            rec["xla_bytes_unscaled"] = float(ca.get("bytes accessed", -1.0))
    except Exception as e:  # pragma: no cover
        rec["xla_cost_error"] = str(e)

    text = compiled.as_text()
    coll = collective_bytes(text)
    rec["hlo_collective_bytes_per_device"] = coll["bytes_per_device"]
    rec["hlo_collective_counts"] = coll["counts"]
    if coll["warnings"]:
        rec["hlo_warnings"] = coll["warnings"][:10]

    # --- model flops (6ND train / 2ND inference; N_active for MoE) ---
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    rec["param_count"] = n_params
    rec["active_param_count"] = n_active
    rec["model_flops"] = mult * n_active * tokens
    rec["tokens_per_step"] = tokens
    rec["status"] = "ok"
    return rec


ALL_MESHES = ("single", "multi")


def iter_cells():
    from repro.configs import ARCH_IDS, SHAPES

    for arch in ARCH_IDS:
        for shape in SHAPES:
            yield arch, shape


def main() -> int:
    # before the first JAX import; the --all children inherit both
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ["JAX_PLATFORMS"] = "cpu"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (python literal), e.g. --set moe_dispatch='scatter'")
    ap.add_argument("--tag", default="", help="artifact suffix for variant runs")
    args = ap.parse_args()

    import ast

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v

    ART_DIR.mkdir(parents=True, exist_ok=True)

    if args.all:
        cells = [
            (a, s, m)
            for a, s in iter_cells()
            for m in (ALL_MESHES if args.mesh == "both" else (args.mesh,))
        ]
        procs: list = []
        failed = []
        for arch, shape, m in cells:
            out = ART_DIR / f"{arch}__{shape}__{m}.json"
            if out.exists() and not args.force:
                continue
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", shape, "--mesh", m,
            ]
            while len(procs) >= args.jobs:
                for p in procs[:]:
                    if p[0].poll() is not None:
                        procs.remove(p)
                        if p[0].returncode != 0:
                            failed.append(p[1])
                            print(f"FAIL {p[1]}", flush=True)
                        else:
                            print(f"done {p[1]}", flush=True)
                time.sleep(1.0)
            procs.append((subprocess.Popen(cmd, stdout=subprocess.DEVNULL), f"{arch}/{shape}/{m}"))
        for p, name in procs:
            p.wait()
            if p.returncode != 0:
                failed.append(name)
                print(f"FAIL {name}", flush=True)
            else:
                print(f"done {name}", flush=True)
        print(f"dry-run complete; {len(failed)} failures: {failed}")
        return 1 if failed else 0

    rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh, "status": "error"}
    try:
        rec = run_cell(args.arch, args.shape, args.mesh, overrides)
    except Exception:
        rec["traceback"] = traceback.format_exc()
        print(rec["traceback"], file=sys.stderr)
    sfx = f"__{args.tag}" if args.tag else ""
    out = ART_DIR / f"{args.arch}__{args.shape}__{args.mesh}{sfx}.json"
    out.write_text(json.dumps(rec, indent=2, default=str))
    print(json.dumps({k: rec[k] for k in ("arch", "shape", "mesh", "status") if k in rec}))
    if rec["status"] == "ok":
        print(f"compile={rec.get('t_compile_s', 0):.1f}s "
              f"flops={rec.get('jaxpr_flops', 0):.3e} "
              f"coll_bytes/dev={rec.get('hlo_collective_bytes_per_device', 0):.3e}")
    return 0 if rec["status"] in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
