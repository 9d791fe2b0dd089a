"""Roofline time of the held experts' grouped GEMMs (their bfloat16 weights
once a layer, the routed rows, the routed FLOPs) over the grouped GEMM
kernels' device time in the traced window (%)."""

from chipbench import expert_readings


def read(run):
    return expert_readings.expert_gemm_roofline_pct(run)
