"""``expert_gmm``'s share of its roofline: least time for the routed
rows' work in the traced decode and chunk steps (engine counters,
``chipbench/kernels/expert_gmm.py``) over the traced time of the ops named
``expert_gmm``, in %."""
from chipbench import moe_counts, peaks, readings, spec


def read(run):
    if run.trace is None:
        return None
    mod = spec.kernel_cost("expert_gmm")
    least = 0.0
    for program, counts in (
            (readings.DECODE_STEP, moe_counts.per_decode_step(run)),
            (readings.CHUNK_STEP, moe_counts.per_chunk_step(run))):
        n = len(run.trace.program_times(program))
        if n and counts is not None:
            least += n * peaks.least_time(*mod.cost(run.arch, *counts),
                                          run.device["kind"])
    kernel_s = moe_counts.kernel_seconds(run.trace, "expert_gmm")
    if least <= 0 or kernel_s <= 0:
        return None
    return 100.0 * least / kernel_s
