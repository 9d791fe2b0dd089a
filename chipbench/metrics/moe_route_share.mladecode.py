"""Device time of the MoE layers' routing and combining operations over the
decode step program's device time in the traced window (%)."""

from chipbench import expert_readings


def read(run):
    return expert_readings.moe_route_share_pct(run)
