"""``flash_prefill_chunk``'s share of its roofline: least time for the
valid tokens' work over the kernel's traced time, in %."""
from chipbench import readings


def read(run):
    return readings.kernel_roofline(run, "flash_prefill_chunk",
                                    readings.CHUNK_STEP)
