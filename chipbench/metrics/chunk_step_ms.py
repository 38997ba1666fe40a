"""Device time per chunk-prefill program in the trace, mean, in ms."""
from chipbench import readings


def read(run):
    return readings.step_ms(run, readings.CHUNK_STEP)
