"""``flash_decode``'s share of its roofline: least time for the live
rows' work over the kernel's traced time, in %."""
from chipbench import readings


def read(run):
    return readings.kernel_roofline(run, "flash_decode",
                                    readings.DECODE_STEP)
