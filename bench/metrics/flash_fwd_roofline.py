"""The Pallas ``flash_fwd`` kernel (causal prefill attention): its least
time per call (causal FLOPs against the bf16 peak, or q, k, v and o bytes
against HBM bandwidth, whichever is larger) over its summed device time in
the trace: percent."""

from bench import flops

KERNEL = "flash_fwd"


def read(run):
    t = run.trace
    if t is None:
        return None
    n, ns = t.kernel_runs(KERNEL)
    if not n or ns <= 0:
        return None
    mix = run.cell.mix
    work, nbytes = flops.flash_fwd_call(run.cell.model, mix.batch, mix.prompt_len)
    least = max(work / run.peaks["bf16_flops"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * n * least / (ns * 1e-9)
