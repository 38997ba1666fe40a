"""``flash_prefill_chunk``: a prompt chunk's valid queries attend the
slot's prefix and, causally, the chunk itself, in every layer.  Counted
from the valid tokens and the live prefix, not the padded chunk or the
arena's rows."""
from __future__ import annotations

# a Pallas kernel is a custom call to this target, with no name of its
# own in the trace; it is the only one in its step program
TARGET = "tpu_custom_call"


def cost(arch, chunk, kv_bytes: int) -> tuple[float, float]:
    """``chunk``: ``(start, size, valid)`` of one chunk call;
    ``kv_bytes``: bytes per stored K or V element."""
    start, _, valid = chunk
    nh, kvh, hd = arch.n_heads, arch.n_kv_heads, arch.hd
    pairs = valid * start + valid * (valid + 1) / 2    # causal (q, k) pairs
    flops = 4.0 * nh * hd * pairs
    nbytes = (2.0 * kvh * hd * kv_bytes * (start + valid)   # K, V rows read
              + 2.0 * nh * hd * 2 * valid)                   # q in, out
    return flops * arch.n_layers, nbytes * arch.n_layers
