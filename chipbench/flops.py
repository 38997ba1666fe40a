"""Model operations of one engine step, counted from the work the
algorithm needs: the valid tokens and live context of each call, never the
padded chunk or the arena's shape."""
from __future__ import annotations


def layer_matmul_params(arch) -> int:
    """Weights one token multiplies by in one dense layer."""
    d, hd = arch.d_model, arch.hd
    attn = d * hd * (arch.n_heads + 2 * arch.n_kv_heads) + arch.n_heads * hd * d
    mlp = (3 if arch.act == "silu_gated" else 2) * d * arch.d_ff
    return attn + mlp


def attention_flops(arch, queries_context) -> float:
    """QK^T and PV over ``(n_queries, context)`` pairs, all layers."""
    per = 4 * arch.n_heads * arch.hd * arch.n_layers
    return float(sum(per * ctx for ctx in queries_context))


def decode_step(arch, lengths) -> float:
    """One decode step: each live slot's token through every layer and
    the head, attending its ``lengths[i]`` live rows."""
    b = len(lengths)
    mm = 2 * b * (arch.n_layers * layer_matmul_params(arch)
                  + arch.d_model * arch.vocab)
    return mm + attention_flops(arch, lengths)


def chunk_step(arch, start: int, valid: int) -> float:
    """One prefill chunk: ``valid`` prompt tokens from ``start``, causal
    over the prefix, and the head for the chunk's last token."""
    mm = 2 * valid * arch.n_layers * layer_matmul_params(arch)
    head = 2 * arch.d_model * arch.vocab
    ctx = [start + i + 1 for i in range(valid)]
    return mm + head + attention_flops(arch, ctx)
