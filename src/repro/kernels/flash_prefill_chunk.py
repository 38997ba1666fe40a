"""Chunk-append prefill attention: one prompt strip against the paged cache.

Chunked prefill is the paper's stripmining discipline applied to prompt
ingestion: instead of one monolithic prefill per prompt length (a new XLA
compile per length — the serving analogue of an issue stall), the prompt is
cut into fixed bucket-size chunks and each chunk attends (a) causally within
itself and (b) fully over the KV prefix already written to its cache slot.
The chunk's own K/V rows are written into the cache *before* the kernel
runs, so the kernel sees one contiguous KV buffer whose live length is
``prefix + chunk`` — exactly :mod:`flash_decode` generalised from one query
row to a strip of ``C`` query rows.

Geometry: grid = (B·KVH, Sk/bk), KV-strip axis innermost with (m, l, acc)
carries in VMEM scratch.  Queries are folded (G·C, hd) so the MXU sees one
2-D matmul per strip; the causal boundary is dynamic (``prefix`` is a
scalar-prefetched SMEM vector read at ``program_id(0)`` — chunk position in
the prompt is runtime data, not a compile key).  Strips entirely beyond
``prefix + C`` are skipped via ``pl.when`` (the ``vl = 0`` fast path); rows
past the live length are tail-predicated.

Quantized-arena support mirrors :mod:`flash_decode`: optional per-row
scale operands (lane-dense (BKV, 1, Sk) rows), dequant fused into the
strip loop — a key row's scale multiplies its score column and its value
row's probability, so the narrow arena is never widened in memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fpc_kernel(pre_ref, q_ref, k_ref, v_ref, *refs,
                scale: float, window: int | None, c: int, g: int,
                bk: int, nk: int, scaled: bool):
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

    prefix = pre_ref[pl.program_id(0)]        # rows live before this chunk
    gc = g * c
    # folded query row r = group * C + i  ->  absolute position prefix + i
    qpos = prefix + jax.lax.broadcasted_iota(jnp.int32, (gc, bk), 0) % c
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (gc, bk), 1)
    mask = kpos <= qpos                       # causal across the boundary
    if window is not None:
        mask &= kpos > qpos - window

    # strip-level skip: whole strip beyond the chunk's last row (vl == 0)
    live = j * bk < prefix + c
    if window is not None:
        live &= (j + 1) * bk > prefix - window

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)      # (G*C, hd)
        k = k_ref[0].astype(jnp.float32)      # (bk, hd)
        v = v_ref[0].astype(jnp.float32)      # (bk, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if scaled:
            # fused dequant: key row j's scale multiplies score column j
            s = s * ks_ref[0]                 # (1, bk) row
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        pv = p * vs_ref[0] if scaled else p   # value row j's scale
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jnp.dot(pv, v,
                                  preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _flush():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def flash_prefill_chunk(q: jax.Array, k: jax.Array, v: jax.Array,
                        prefix: jax.Array, *, window: int | None = None,
                        scale: float | None = None, bk: int = 512,
                        scales: tuple[jax.Array, jax.Array] | None = None,
                        interpret: bool = False) -> jax.Array:
    """q: (BKV, G, C, D) one chunk of queries per row-group; k/v:
    (BKV, Sk, D) the cache arena with the chunk's K/V already written at
    rows [prefix, prefix + C); prefix: (BKV,) int32 rows live before the
    chunk.  Returns (BKV, G, C, D).

    GQA folding is the caller's job (ops.py): BKV = batch·kv_heads, G =
    n_heads // kv_heads.  Requires Sk % bk == 0 (ops.py pads; padded rows
    sit beyond every live length, killed by the causal/tail mask).

    ``scales``: optional (k_scale, v_scale) pair of (BKV, Sk) f32 dequant
    scales for a quantized cache — folded like K/V minus the head dim.
    """
    bkv, g, c, d = q.shape
    bkv_k, sk, dk = k.shape
    assert bkv == bkv_k and d == dk, (q.shape, k.shape)
    bk = min(bk, sk)
    if sk % bk:
        raise ValueError(f"Sk={sk} unaligned to block bk={bk}")
    scale = scale if scale is not None else d ** -0.5
    nk = sk // bk
    qf = q.reshape(bkv, g * c, d)
    scaled = scales is not None
    # index maps take the scalar-prefetched prefix ref as a trailing arg
    in_specs = [
        pl.BlockSpec((1, g * c, d), lambda b, j, pre: (b, 0, 0)),
        pl.BlockSpec((1, bk, d), lambda b, j, pre: (b, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, j, pre: (b, j, 0)),
    ]
    operands = [prefix.astype(jnp.int32), qf, k, v]
    if scaled:
        in_specs += [pl.BlockSpec((1, 1, bk), lambda b, j, pre: (b, 0, j)),
                     pl.BlockSpec((1, 1, bk), lambda b, j, pre: (b, 0, j))]
        operands += [sc.astype(jnp.float32)[:, None, :] for sc in scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bkv, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g * c, d), lambda b, j, pre: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g * c,), jnp.float32),       # running max m
            pltpu.VMEM((g * c,), jnp.float32),       # running denom l
            pltpu.VMEM((g * c, d), jnp.float32),     # running accumulator
        ])
    out = pl.pallas_call(
        functools.partial(_fpc_kernel, scale=scale, window=window,
                          c=c, g=g, bk=bk, nk=nk, scaled=scaled),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bkv, g * c, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="flash_prefill_chunk",
        interpret=interpret,
    )(*operands)
    return out.reshape(bkv, g, c, d)
