"""Blockwise attention with online softmax — chaining + strip-mining (C5+C7).

The paper's chaining insight (multiply unit feeding the reduction unit so
cycles scale with elements, not instructions) is exactly the flash-attention
trick: QKᵀ partial products chain into a *running* softmax reduction and PV
accumulation, so the (Sq × Sk) score matrix is never materialised in HBM —
the strip-mined KV axis is the paper's VLEN loop with an online-reduction
carry.

Geometry: grid = (batch·heads, Sq/bq, Sk/bk), innermost axis walks KV strips;
carries (m, l, acc) live in VMEM scratch, exactly the operand-queue residency
argument of the matmul kernel.  Causal and sliding-window predication (C3)
is applied as block masks; fully-masked KV strips are skipped via ``pl.when``
(the RVV ``vl=0`` fast path).  Ragged lengths arrive zero-padded to whole
blocks (the RVV tail): keys past ``kv_len`` are predicated off, and queries
are right-aligned on the real lengths through ``q_offset``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: int | None,
               bq: int, bk: int, nk: int, kv_len: int, q_offset: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions; queries right-aligned with the KV sequence
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < kv_len                          # tail predication
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window

    # block-level skip: strip has no live element (vl == 0 fast path)
    first_qpos = i * bq + q_offset
    last_qpos = first_qpos + bq - 1
    live = jnp.asarray(True)
    if causal:
        live &= j * bk <= last_qpos
    if window is not None:
        live &= (j + 1) * bk - 1 > first_qpos - window

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jnp.dot(p, v_ref[0].astype(jnp.float32),
                                  preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _flush():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    kv_len: int, q_offset: int,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, bq: int = 256,
                    bk: int = 512, interpret: bool = False) -> jax.Array:
    """q: (BH, Sq, D), k/v: (BH, Sk, D) -> (BH, Sq, D).

    GQA head-sharing is the caller's job (repeat/arrange KV to BH).
    Requires Sq % bq == Sk % bk == 0 (ops.py pads otherwise).  ``kv_len``
    is the number of real (unpadded) keys; ``q_offset`` is the absolute
    position of query row 0 (real key count minus real query count).
    """
    bhq, sq, d = q.shape
    bhk, sk, dk = k.shape
    assert bhq == bhk and d == dk, (q.shape, k.shape)
    bq = min(bq, sq)
    bk = min(bk, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"Sq={sq},Sk={sk} unaligned to blocks ({bq},{bk})")
    scale = scale if scale is not None else d ** -0.5
    nk = sk // bk
    return pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, nk=nk, kv_len=kv_len,
                          q_offset=q_offset),
        grid=(bhq, sq // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max m
            pltpu.VMEM((bq,), jnp.float32),      # running denom l
            pltpu.VMEM((bq, d), jnp.float32),    # running accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
