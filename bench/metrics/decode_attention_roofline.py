"""The Pallas ``decode_attention`` kernel: its least time per call (q and o,
K and V read up to the cache length, against HBM bandwidth; or its FLOPs
against the bf16 peak, whichever is larger) over its summed device time in
the trace: percent."""

from bench import flops

KERNEL = "decode_attention"


def read(run):
    t, kv = run.trace, run.window.decode_kv_lens
    if t is None or not kv:
        return None
    n, ns = t.kernel_runs(KERNEL)
    if not n or ns <= 0:
        return None
    work, nbytes = flops.decode_attention_call(run.cell.model, run.cell.mix.batch,
                                               sum(kv) / len(kv))
    least = max(work / run.peaks["bf16_flops"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * n * least / (ns * 1e-9)
