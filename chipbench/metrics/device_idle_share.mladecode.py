"""Share of the traced window in which no operation ran on the device (%)."""

from chipbench import readings


def read(run):
    return readings.idle_share_pct(run)
