"""Model FLOPs of the traced window's output tokens over the window and the
chip's peak (%)."""

from chipbench import readings


def read(run):
    return readings.step_mfu_pct(run)
