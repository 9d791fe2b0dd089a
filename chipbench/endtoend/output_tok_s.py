"""Output tokens produced inside the window, over the window (host clock)."""

from chipbench import readings


def read(run):
    r = run.record
    return readings.window_tokens(run) / (r.t1 - r.t0)
