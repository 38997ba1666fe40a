"""Flash-decode: one-token attention over the resident KV arena, read in
place (C3+C5).

The serving decode step attends a single query token per slot against that
slot's live KV rows.  Per-slot sequences in a continuous-batching engine
have *different* lengths, so the kernel takes a ``lengths`` vector and
applies tail predication per slot (the RVV ``vl`` of the paper, one ``vl``
per batch row).

The kernel reads the arena as it is stored — the stacked ``(L, B, S, KVH,
hd)`` leaf of every layer, left in HBM — with the layer index and the
lengths scalar-prefetched into SMEM (``PrefetchScalarGridSpec``).  Grid =
(B,): each program loops over exactly its slot's live strips of ``bk``
rows, double-buffering one contiguous ``(bk, KVH, hd)`` strip DMA ahead of
the strip it computes, with the (m, l, acc) online-softmax carries in VMEM
scratch.  No slice, pad or transpose of the arena is made outside the
kernel, and no dead strip moves: a slot with n live rows fetches
``ceil(n / bk)`` strips (none when n is 0), not ``S / bk``;
:func:`strip_counts` counts them for the engine's spans.  The arena's last
strip is fetched at its own, shorter length; the buffer rows past it hold
no defined value.

One dot serves all KV heads: the strip is taken as ``bk * KVH`` rows of
``hd`` (row ``s * KVH + h``), every query head is scored against every row,
and a query head's scores against another head's rows are masked.  That
reads each strip contiguously; slicing one head's rows out of the strip
costs more than the extra dot work (PERF.md §5).  Rows at or past the
slot's live length (and, under a sliding window, before it) are masked in
the scores and zeroed in V.

The decode step's own new K/V row is an operand of its own (``rows``): the
carries start from it, then the arena rows [0, n) follow — the same
softmax over the same n + 1 keys as writing the row first, without a
patched copy of the arena.

Quantized-arena support (core/kv_format.py — the paper's multi-precision
lanes): an optional per-row scale pair rides along, read from the ``(L, B,
KVH, S)`` view of the ``(L, B, S, KVH)`` scale leaves (the TPU keeps those
leaves S-minor, so the view is free) one slot's layer row at a time — a
32nd of an int8 K row's bytes, so the whole row moves.  Each K/V strip
widens to f32 *in-register* and multiplies by its rows' scales right
before its dot, so the narrow arena is the only thing that ever lives in
memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BK = 256


def strip_counts(live_rows: np.ndarray, rows: int,
                 bk: int = DEFAULT_BK) -> tuple[int, int]:
    """(strips fetched, strips of the whole arena) of one layer's decode
    kernel without a window.

    ``live_rows``: each slot's live arena rows, on the host; ``rows``: the
    arena's S."""
    bk = min(bk, rows)
    fetched = (-(-live_rows // bk)).sum()
    return int(fetched), len(live_rows) * -(-rows // bk)


def _fd_kernel(layer_ref, len_ref, *refs, scale: float, bk: int, rows: int,
               kvh: int, windowed: bool, has_row: bool, scaled: bool):
    refs = list(refs)
    take = lambda k: [refs.pop(0) for _ in range(k)]
    lo_ref, = take(1) if windowed else (None,)
    q_ref, = take(1)
    kr_ref, vr_ref = take(2) if has_row else (None, None)
    ks_ref, vs_ref = take(2) if scaled else (None, None)
    k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref = refs
    b, li = pl.program_id(0), layer_ref[0]
    n = len_ref[b]                                   # this slot's vl
    lo = lo_ref[b] if windowed else None
    nh, hd = q_ref.shape[1], q_ref.shape[2]
    g, w = nh // kvh, bk * kvh
    # the slot's live strips [first, end)
    end = (n + bk - 1) // bk
    first = jnp.minimum(jnp.maximum(lo, 0) // bk, end) if windowed else 0

    nk, tail = -(-rows // bk), rows - (rows - 1) // bk * bk

    def dma(j, slot, wait: bool):
        """Start, or wait for, strip ``j``'s copies into buffer ``slot``;
        the arena's last strip holds only its ``tail`` rows."""
        def go(size):
            rows_j, dst = pl.ds(j * bk, size), pl.ds(0, size)
            pairs = [(k_hbm.at[li, b, rows_j], kbuf.at[slot, dst]),
                     (v_hbm.at[li, b, rows_j], vbuf.at[slot, dst])]
            for i, (src, to) in enumerate(pairs):
                c = pltpu.make_async_copy(src, to, sem.at[i, slot])
                c.wait() if wait else c.start()
        if tail == bk:
            go(bk)
        else:
            pl.when(j < nk - 1)(lambda: go(bk))
            pl.when(j == nk - 1)(lambda: go(tail))

    @pl.when(first < end)
    def _prefetch():
        dma(first, 0, wait=False)

    if has_row:
        # the new token's row seeds the carries: m = its score, l = 1
        for h in range(kvh):
            heads = pl.ds(h * g, g)
            kr = kr_ref[0, pl.ds(h, 1), :].astype(jnp.float32)  # (1, hd)
            vr = vr_ref[0, pl.ds(h, 1), :].astype(jnp.float32)
            q = q_ref[0, heads, :].astype(jnp.float32)          # (G, hd)
            m_ref[heads, :] = (q * kr).sum(axis=-1, keepdims=True) * scale
            acc_ref[heads, :] = jnp.broadcast_to(vr, (g, hd))
        l_ref[...] = jnp.ones_like(l_ref)
    else:
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                            # (H, hd)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    own = col % kvh == jax.lax.broadcasted_iota(jnp.int32, (nh, 1), 0) // g
    vrow = jax.lax.broadcasted_iota(jnp.int32, (w, hd), 0) // kvh

    def strip(j, carry):
        slot = (j - first) % 2

        @pl.when(j + 1 < end)
        def _next():
            dma(j + 1, 1 - slot, wait=False)

        dma(j, slot, wait=True)
        kpos = j * bk + col // kvh                               # (1, w)
        mask = own & (kpos < n)                                  # tail vl
        if windowed:
            mask &= kpos >= lo
        k = kbuf[slot].astype(jnp.float32)                       # (bk,KVH,hd)
        v = vbuf[slot].astype(jnp.float32)
        if scaled:                   # fused dequant of each key/value row
            cols = pl.ds(pl.multiple_of(j * bk, bk), bk)
            k = k * jnp.swapaxes(ks_ref[0, 0, :, cols], 0, 1)[:, :, None]
            v = v * jnp.swapaxes(vs_ref[0, 0, :, cols], 0, 1)[:, :, None]
        k = k.reshape(w, hd)
        v = jnp.where(j * bk + vrow < n, v.reshape(w, hd), 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale          # (H, w)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                      # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(first, end, strip, 0)
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 lengths: jax.Array, layer: jax.Array, *,
                 rows: tuple[jax.Array, jax.Array] | None = None,
                 window_lo: jax.Array | None = None,
                 scale: float | None = None, bk: int = DEFAULT_BK,
                 scales: tuple[jax.Array, jax.Array] | None = None,
                 interpret: bool = False) -> jax.Array:
    """q: (B, H, hd) one query token per slot, its consecutive H / KVH
    heads sharing a KV head; k/v: (L, B, S, KVH, hd) the stacked arena,
    read in place at ``layer`` (int32 scalar); lengths: (B,) int32 arena
    rows [0, n) each slot attends (at most S).  Returns (B, H, hd).

    ``rows``: optional (k_row, v_row) of (B, KVH, hd), the decode step's
    new token, attended ahead of the arena rows (its key position is n).
    ``window_lo``: optional (B,) int32 first key position inside a sliding
    window; the new row is always inside it.  ``scales``: optional
    (k_scale, v_scale) of (L, B, S, KVH) f32 dequant scales of a quantized
    arena; ``rows`` are then already dequantized.
    """
    nb, nh, hd = q.shape
    s, kvh = k.shape[2], k.shape[3]
    assert (nb, hd) == (k.shape[1], k.shape[4]) and nh % kvh == 0, (
        q.shape, k.shape)
    bk = min(bk, s)
    scale = scale if scale is not None else hd ** -0.5
    windowed = window_lo is not None
    has_row = rows is not None
    scaled = scales is not None

    prefetch = [jnp.asarray(layer, jnp.int32).reshape(1),
                lengths.astype(jnp.int32)]
    if windowed:
        prefetch.append(window_lo.astype(jnp.int32))
    per_slot = lambda b, *_: (b, 0, 0)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, nh, hd), per_slot)]
    operands = [q]
    if has_row:
        in_specs += [pl.BlockSpec((1, kvh, hd), per_slot)] * 2
        operands += list(rows)
    if scaled:
        # the S-minor view of the (L, B, S, KVH) scale leaves: the slot's
        # lane-dense (KVH, S) scales of the layer, in a block of whole
        # strips (the lanes past S hold no defined value)
        in_specs += [pl.BlockSpec((1, 1, kvh, -(-s // bk) * bk),
                                  lambda b, li, *_: (li[0], b, 0, 0))] * 2
        operands += [jnp.swapaxes(sc.astype(jnp.float32), 2, 3)
                     for sc in scales]
    in_specs += [hbm, hbm]
    operands += [k, v]
    scratch = [pltpu.VMEM((2, bk, kvh, hd), k.dtype),
               pltpu.VMEM((2, bk, kvh, hd), v.dtype),
               pltpu.SemaphoreType.DMA((2, 2)),
               pltpu.VMEM((nh, 1), jnp.float32),      # running max m
               pltpu.VMEM((nh, 1), jnp.float32),      # running denom l
               pltpu.VMEM((nh, hd), jnp.float32)]     # running accumulator
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nh, hd), per_slot),
        scratch_shapes=scratch)
    return pl.pallas_call(
        functools.partial(_fd_kernel, scale=scale, bk=bk, rows=s, kvh=kvh,
                          windowed=windowed, has_row=has_row, scaled=scaled),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, nh, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="flash_decode",
        interpret=interpret,
    )(*prefetch, *operands)
