"""Share of the traced window in which no op ran on the device (averaged
over the cell's chips), in percent."""


def read(run):
    t = run.trace
    if t is None or not t.ops or t.window_ns <= 0:
        return None
    return 100.0 * (1.0 - t.mean_busy_ns() / t.window_ns)
