"""Flash-decode: one-token attention over a length-masked KV cache (C3+C5).

The serving decode step attends a single query token against the whole KV
cache of its slot.  Per-slot sequences in a continuous-batching engine have
*different* lengths, so the kernel takes a ``lengths`` vector and applies
tail predication per slot (the RVV ``vl`` of the paper, one ``vl`` per
batch row) — slots whose cache is short simply mask off the tail strips,
and fully-dead strips are skipped via ``pl.when`` (the ``vl=0`` fast path).

Like :mod:`flash_attention`, the KV axis is strip-mined with an online
softmax carry; GQA grouping is preserved so the kernel reads each KV head
once for its ``group`` query heads.  Grid = (B·KVH, Sk/bk), the KV-strip
axis innermost with the (m, l, acc) carries in VMEM scratch.

The per-row ``lengths`` vector is scalar-prefetched into SMEM
(``PrefetchScalarGridSpec``): the whole (BKV,) vector is resident for the
grid, and each program reads its own row's ``vl`` at ``program_id(0)``.

Quantized-arena support (core/kv_format.py — the paper's multi-precision
lanes): an optional per-row scale pair rides along as two extra VMEM
operands and dequant fuses into the inner loop — each K/V strip widens to
f32 *in-register* right before its MXU dot, so the narrow arena is the
only thing that ever lives in memory.  Scales travel as lane-dense
(BKV, 1, Sk) rows (a (1, bk) strip block meets the TPU's last-two-dims
tiling rule); since a scale is per key row, it multiplies the score
column of that key (``s * ks``) and the probability feeding that value
row (``p * vs``) — the same products as scaling K and V rows.

The KV-sequence axis is the one sharded over lanes at the system level
(``kv_seq`` in core/lanes.py): each lane runs this kernel over its local KV
strip and the cross-lane softmax combine is a tiny 3-step reduction (C4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fd_kernel(len_ref, q_ref, k_ref, v_ref, *refs,
               scale: float, window: int | None, bk: int, nk: int,
               scaled: bool):
    if scaled:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[pl.program_id(0)]               # this row's vl
    g = q_ref.shape[1]
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
    mask = kpos < length                             # tail predication
    if window is not None:
        mask &= kpos >= length - window

    # strip-level skip: whole strip beyond the live length (vl == 0)
    live = j * bk < length
    if window is not None:
        live &= (j + 1) * bk > length - window

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)             # (G, hd)
        k = k_ref[0].astype(jnp.float32)             # (bk, hd)
        v = v_ref[0].astype(jnp.float32)             # (bk, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if scaled:
            # fused dequant: key row j's scale multiplies score column j
            s = s * ks_ref[0]                        # (1, bk) row
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        pv = p * vs_ref[0] if scaled else p          # value row j's scale
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jnp.dot(pv, v,
                                  preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _flush():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 lengths: jax.Array, *, window: int | None = None,
                 scale: float | None = None, bk: int = 512,
                 scales: tuple[jax.Array, jax.Array] | None = None,
                 interpret: bool = False) -> jax.Array:
    """q: (BKV, G, D) one query token per row-group; k/v: (BKV, Sk, D);
    lengths: (BKV,) int32 live-KV count per row.  Returns (BKV, G, D).

    GQA folding is the caller's job (ops.py): BKV = batch·kv_heads and G =
    n_heads // kv_heads, so each KV row is read once for its G queries.
    Requires Sk % bk == 0 (ops.py pads; padded keys sit beyond every
    ``lengths`` so the tail mask kills them).

    ``scales``: optional (k_scale, v_scale) pair of (BKV, Sk) f32 dequant
    scales for a quantized cache — folded like K/V minus the head dim.
    """
    bkv, g, d = q.shape
    bkv_k, sk, dk = k.shape
    assert bkv == bkv_k and d == dk, (q.shape, k.shape)
    bk = min(bk, sk)
    if sk % bk:
        raise ValueError(f"Sk={sk} unaligned to block bk={bk}")
    scale = scale if scale is not None else d ** -0.5
    nk = sk // bk
    scaled = scales is not None
    # index maps take the scalar-prefetched lengths ref as a trailing arg
    in_specs = [
        pl.BlockSpec((1, g, d), lambda b, j, lens: (b, 0, 0)),
        pl.BlockSpec((1, bk, d), lambda b, j, lens: (b, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, j, lens: (b, j, 0)),
    ]
    operands = [lengths.astype(jnp.int32), q, k, v]
    if scaled:
        in_specs += [pl.BlockSpec((1, 1, bk), lambda b, j, lens: (b, 0, j)),
                     pl.BlockSpec((1, 1, bk), lambda b, j, lens: (b, 0, j))]
        operands += [sc.astype(jnp.float32)[:, None, :] for sc in scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bkv, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g, d), lambda b, j, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),       # running max m
            pltpu.VMEM((g,), jnp.float32),       # running denom l
            pltpu.VMEM((g, d), jnp.float32),     # running accumulator
        ])
    return pl.pallas_call(
        functools.partial(_fd_kernel, scale=scale, window=window,
                          bk=bk, nk=nk, scaled=scaled),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="flash_decode",
        interpret=interpret,
    )(*operands)
