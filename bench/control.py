#!/usr/bin/env python3
"""Read a cell's correctness numbers over many seeds, for the program and
for its control, in one process on the chip.

    python3 bench/control.py --workload qwen1.5-4b.decode --seeds 101-112 \
        --control-seeds 101-103 [--out FILE]

Program seeds: weights drawn from the seed, a window at the cell's own load
that runs until as many requests have finished as a run checks, the served
weights freed, then the reference over the seeded sample: the widest gap by
which a served token's logit lies below the reference's best
(``logit_gap``).  The largest is the cell's lower reading.

Control seeds: a whole run (``harness.run_cell``) with that short window,
the reference computed in float8 (``quant="fp8"``) put in the program's
place, judged against the limit in ``bench/cells/<cell>.json`` as any run
is.  Each has to read ``correct`` false; the smallest ``logit_gap`` is the
upper reading.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]


def seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def readings(workload: str, seed_list, root: Path = ROOT, need_chip: bool = True):
    """Yield the program's reading on each seed, one server for all."""
    from bench import harness

    cell = harness.load_cell(workload, root)
    harness.setup_compile_cache()
    if need_chip:
        harness.devices(cell.chips)
    server = None
    for seed in seed_list:
        t0 = time.perf_counter()
        served = harness.Served(cell, seed, server)
        server = served.server
        window = served.serve(1e9, finish=int(cell.check["sample_requests"]))
        served.free()
        picks = harness.sample(window, cell, seed)
        out = harness.gaps(cell, served.key, served.vocab_rows, seed, window, picks)
        yield {"workload": workload, "seed": seed, "requests": len(picks), **out,
               "seconds": time.perf_counter() - t0}


def control_runs(workload: str, seed_list, root: Path = ROOT, need_chip: bool = True):
    """Yield the control's run on each seed, as the harness judges it."""
    from bench import harness

    cell = harness.load_cell(workload, root)
    for seed in seed_list:
        out = harness.run_cell(workload, seed, 1e9, False, time.perf_counter(), root=root,
                               need_chip=need_chip, control=True,
                               finish=int(cell.check["sample_requests"]))
        yield {"workload": workload, "seed": seed, "control": True, "correct": out["correct"],
               **{k: c["value"] for k, c in out["checks"].items()},
               "limits": {k: c["limit"] for k, c in out["checks"].items()}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--out", type=Path)
    args = ap.parse_args()
    rows = []
    for row in itertools.chain(readings(args.workload, args.seeds),
                               control_runs(args.workload, args.control_seeds)):
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            args.out.parent.mkdir(parents=True, exist_ok=True)
            with args.out.open("a") as f:
                f.write(json.dumps(row) + "\n")
    prog = [r["logit_gap"] for r in rows if not r.get("control")]
    ctl = [r for r in rows if r.get("control")]
    print(json.dumps({
        "workload": args.workload, "seeds": len(prog), "lower": max(prog, default=None),
        "control_seeds": len(ctl), "upper": min((r["logit_gap"] for r in ctl), default=None),
        "control_correct": [r["correct"] for r in ctl]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
