"""Active model operations of the traced MoE decode steps over their
device time times the chip's bf16 peak, in %.  Active: attention
projections and attention over each slot's live rows, the router at its
full width, the rows routed to the held experts (engine counters) through
their three matrices, and the head."""
import numpy as np

from chipbench import flops, moe_counts, readings


def step_flops(arch, lengths, rows_here: float) -> float:
    b, d, hd = len(lengths), arch.d_model, arch.hd
    proj = d * hd * (arch.n_heads + 2 * arch.n_kv_heads) + arch.n_heads * hd * d
    router = d * arch.moe.n_experts
    return (2.0 * b * (arch.n_layers * (proj + router) + d * arch.vocab)
            + flops.attention_flops(arch, lengths)
            + 2.0 * 3 * d * arch.moe.d_ff_expert * rows_here)


def read(run):
    counts = moe_counts.per_decode_step(run)
    if counts is None:
        return None
    work = [step_flops(run.arch, d, counts[0])
            for d in readings.decode_calls(run)]
    times = run.trace.program_times(readings.DECODE_STEP) if run.trace \
        else []
    if not work or not times:
        return None
    # the host records' mean work per step, times the traced step count
    return (100.0 * float(np.mean(work)) * len(times)
            / (float(np.sum(times)) * run.peaks["flops_bf16"]))
