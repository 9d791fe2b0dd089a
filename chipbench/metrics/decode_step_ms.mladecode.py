"""Device time per execution of the decode step program in the traced window (ms)."""

from chipbench import readings


def read(run):
    return readings.decode_step_ms(run)
