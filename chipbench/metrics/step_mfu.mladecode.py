"""Model FLOPs of the traced window's tokens, routed experts at the share
the program counted, over the window and the chip's peak (%)."""

from chipbench import expert_readings


def read(run):
    return expert_readings.step_mfu_pct(run)
