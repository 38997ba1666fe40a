"""Model operations of the traced decode steps over their device time
times the chip's bf16 peak, in %."""
from chipbench import readings


def read(run):
    return readings.step_mfu(run, readings.DECODE_STEP)
