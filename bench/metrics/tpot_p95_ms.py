"""95th percentile of every gap between consecutive tokens of a request,
over all requests of the window (both tokens inside it).  The requests of a
batch get their tokens at the same host time, so each gap counts once per
request of the batch."""

import numpy as np


def read(run):
    w, B = run.window, run.cell.mix.batch
    gaps = [b - a for batch in w.batches
            for a, b in zip(batch.times, batch.times[1:]) if b <= w.end]
    if not gaps:
        return None
    return float(np.percentile(np.repeat(gaps, B), 95)) * 1e3
