"""Device time per decode-step program in the trace, mean, in ms."""
from chipbench import readings


def read(run):
    return readings.step_ms(run, readings.DECODE_STEP)
