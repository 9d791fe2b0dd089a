"""Median gap between consecutive output tokens of a request, over every
gap inside the window (host clock)."""

from chipbench import readings, stats


def read(run):
    xs = readings.inter_token_gaps_ms(run)
    return stats.percentile(xs, 50) if xs else None
