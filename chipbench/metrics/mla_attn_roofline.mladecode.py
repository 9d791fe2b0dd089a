"""Roofline time of the live contexts' latent attention (their latent rows
read once, at the chip's peak bandwidth) over the paged latent kernel's
device time in the traced window (%)."""

from chipbench import expert_readings


def read(run):
    return expert_readings.mla_attn_roofline_pct(run)
