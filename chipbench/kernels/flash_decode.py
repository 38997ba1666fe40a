"""``flash_decode``: one query token per live slot against that slot's
live K/V rows, in every layer.  The operations and HBM bytes the algorithm
needs, counted from the live lengths and never from the arena's rows, so
a kernel that skips dead rows reads as closer to its roofline."""
from __future__ import annotations

# a Pallas kernel is a custom call to this target, with no name of its
# own in the trace; it is the only one in its step program
TARGET = "tpu_custom_call"


def cost(arch, lengths, kv_bytes: int) -> tuple[float, float]:
    """``lengths``: live K/V rows of each active slot at one decode step;
    ``kv_bytes``: bytes per stored K or V element."""
    rows = float(sum(lengths))
    nh, kvh, hd = arch.n_heads, arch.n_kv_heads, arch.hd
    flops = 4.0 * nh * hd * rows                       # q.k and p.v
    nbytes = (2.0 * kvh * hd * kv_bytes * rows         # K and V rows
              + 2.0 * nh * hd * 2 * len(lengths))      # q in, out (bf16)
    return flops * arch.n_layers, nbytes * arch.n_layers
