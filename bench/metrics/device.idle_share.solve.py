"""1 - device busy / traced window (%) in a solve cell, mean over chips.
Busy is the union of the chip's op intervals."""


def read(run):
    t = run.trace
    if run.kind != "solve" or t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.mean_busy_s() / t.window_s)
