"""95th percentile, over every request whose first token reached the host
inside the window, of the time from when it was due (its batch submitted)
to that first token."""

import numpy as np


def read(run):
    w, B = run.window, run.cell.mix.batch
    ttft = [b.times[0] - b.due for b in w.batches if b.times and b.times[0] <= w.end]
    if not ttft:
        return None
    return float(np.percentile(np.repeat(ttft, B), 95)) * 1e3
