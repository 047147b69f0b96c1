"""Model FLOPs of the decode steps run in the trace (closed form at the
mean cache length of the traced steps), over their device time, over the
chip's bf16 peak: percent."""

from bench import flops

PROGRAM = "jit_decode_step"


def read(run):
    t, kv = run.trace, run.window.decode_kv_lens
    if t is None or not kv:
        return None
    n, ns = t.module_runs(PROGRAM)
    if not n or ns <= 0:
        return None
    work = n * flops.decode_flops(run.cell.model, run.cell.mix.batch, sum(kv) / len(kv))
    return 100.0 * work / (ns * 1e-9) / run.peaks["bf16_flops"]
