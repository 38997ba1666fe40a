"""Share of the traced window in which no operation ran on the device
(1 - busy union / window), in %."""
from chipbench import readings


def read(run):
    return readings.idle_share(run)
