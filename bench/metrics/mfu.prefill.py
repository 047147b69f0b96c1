"""Model FLOPs of one prefill (closed form, ``bench.flops``) times the
prefill programs run in the trace, over their device time, over the chip's
bf16 peak: percent."""

from bench import flops

PROGRAM = "jit_prefill"


def read(run):
    t = run.trace
    if t is None:
        return None
    n, ns = t.module_runs(PROGRAM)
    if not n or ns <= 0:
        return None
    mix = run.cell.mix
    work = n * flops.prefill_flops(run.cell.model, mix.batch, mix.prompt_len)
    return 100.0 * work / (ns * 1e-9) / run.peaks["bf16_flops"]
