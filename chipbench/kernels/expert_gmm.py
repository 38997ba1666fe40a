"""``expert_gmm``: the rows routed to the experts held here, through each
expert's gate, up and down matrices, in every layer.  The operations and
HBM bytes the algorithm needs, counted from the engine's routing counters:
the touched experts' weights once each, and each routed row in and out of
every product — never a padded row tile or an expert without rows."""
from __future__ import annotations

# a Pallas kernel is a custom call to this target, with no name of its
# own in the trace
TARGET = "tpu_custom_call"


def cost(arch, rows_here: float, experts_touched: float,
         w_bytes: int = 2) -> tuple[float, float]:
    """``rows_here``: (token, choice) rows routed to held experts in one
    step, summed over layers; ``experts_touched``: held experts with a row,
    summed over layers; ``w_bytes``: bytes per weight and activation."""
    d, f = arch.d_model, arch.moe.d_ff_expert
    flops = 2.0 * 3 * d * f * rows_here
    nbytes = (3.0 * d * f * w_bytes * experts_touched     # weights
              + 3.0 * (d + f) * w_bytes * rows_here)      # rows in and out
    return flops, nbytes
