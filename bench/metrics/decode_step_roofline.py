"""The least time of a decode step (the larger of its FLOPs over the bf16
peak and its least HBM bytes over the HBM bandwidth: weights once, K/V read
up to the cache length, the new K/V written) over its device time in the
trace: percent."""

from bench import flops

PROGRAM = "jit_decode_step"


def read(run):
    t, kv = run.trace, run.window.decode_kv_lens
    if t is None or not kv:
        return None
    n, ns = t.module_runs(PROGRAM)
    if not n or ns <= 0:
        return None
    m, B, L = run.cell.model, run.cell.mix.batch, sum(kv) / len(kv)
    least = max(flops.decode_flops(m, B, L) / run.peaks["bf16_flops"],
                flops.decode_min_bytes(m, B, L) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * n * least / (ns * 1e-9)
