"""Roofline time of the traced window's GEMMs over the Pallas GEMM kernels'
device time (%)."""

from chipbench import readings


def read(run):
    return readings.gemm_roofline_pct(run)
