"""Roofline time of the live contexts' paged attention over the paged-attention
kernel's device time in the traced window (%)."""

from chipbench import readings


def read(run):
    return readings.paged_attn_roofline_pct(run)
