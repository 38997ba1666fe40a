"""Public kernel API: backend dispatch + tail padding (predication, C3).

Every op has three executable paths:

  * ``pallas``    — the TPU kernel (pl.pallas_call, BlockSpec VMEM tiling),
  * ``interpret`` — the same kernel body interpreted on CPU (tests),
  * ``ref``       — scalable pure-jnp implementation (CPU dry-run + autodiff
                    path; for attention/SSD these are *blockwise* versions
                    built on core.stripmine, not the naive oracles in
                    ref.py, so 32k-524k sequences lower with bounded memory).

``set_mode()`` pins a path; ``auto`` picks pallas on TPU backends and ref
elsewhere (this CPU container always takes ref unless a test asks for
interpret).  Non-aligned shapes are zero-padded here — the RVV tail —
so the kernels stay branch-free; ``flash_decode`` alone masks its own
tail strip, since it reads the KV arena in place.
"""
from __future__ import annotations

from typing import Literal, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import masking, stripmine
from repro.kernels import conv2d as _conv2d
from repro.kernels import dotp as _dotp
from repro.kernels import expert_gmm as _egmm
from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import flash_prefill_chunk as _fpc
from repro.kernels import matmul as _matmul
from repro.kernels import ref
from repro.kernels import ssd as _ssd

Mode = Literal["auto", "pallas", "interpret", "ref"]
_MODE: Mode = "auto"


def set_mode(mode: Mode) -> None:
    global _MODE
    _MODE = mode


def get_mode() -> Mode:
    return _MODE


def _resolved() -> str:
    if _MODE != "auto":
        return _MODE
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

def matmul(a: jax.Array, b: jax.Array, *, bm: int = _matmul.DEFAULT_BM,
           bk: int = _matmul.DEFAULT_BK, bn: int = _matmul.DEFAULT_BN,
           mode: Optional[Mode] = None) -> jax.Array:
    mode = mode or _resolved()
    if mode == "ref":
        return ref.matmul(a, b).astype(a.dtype)
    m, k = a.shape
    _, n = b.shape
    bm_, bk_, bn_ = min(bm, m), min(bk, k), min(bn, n)
    ap = _pad_to(_pad_to(a, bm_, 0), bk_, 1)
    bp = _pad_to(_pad_to(b, bk_, 0), bn_, 1)
    out = _matmul.matmul(ap, bp, bm=bm_, bk=bk_, bn=bn_,
                         interpret=(mode == "interpret"))
    return out[:m, :n]


# ---------------------------------------------------------------------------
# grouped matmul over the experts held here
# ---------------------------------------------------------------------------

def expert_gmm(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array, *,
               layer: Optional[jax.Array] = None,
               mode: Optional[Mode] = None) -> jax.Array:
    """``lhs`` (M, K) rows sorted by expert (expert g owns ``sizes[g]``
    consecutive rows), ``rhs`` (G, K, N), or (L, G, K, N) read at ``layer``
    -> (M, N) in ``lhs``'s dtype, f32 accumulation.  Rows past
    ``sizes.sum()`` hold no defined value on the kernel path.  The row
    buffer is padded to the kernel's row tile."""
    mode = mode or _resolved()
    if layer is None:
        rhs, layer = rhs[None], 0
    if mode == "ref":
        return ref.expert_gmm(lhs, rhs[layer], sizes).astype(lhs.dtype)
    m, k = lhs.shape
    tm = _egmm.row_tile(m)
    tn = _egmm.col_tile(k, rhs.shape[-1], rhs.dtype.itemsize)
    out = _egmm.expert_gmm(_pad_to(lhs, tm, 0), rhs, sizes,
                           jnp.asarray(layer, jnp.int32), tm=tm, tn=tn,
                           interpret=(mode == "interpret"))
    return out[:m]


# ---------------------------------------------------------------------------
# dot product (chained mul+reduce)
# ---------------------------------------------------------------------------

def dotp(a: jax.Array, b: jax.Array, *, strip: int = _dotp.DEFAULT_STRIP,
         mode: Optional[Mode] = None) -> jax.Array:
    mode = mode or _resolved()
    if mode == "ref":
        return ref.dotp(a, b)
    (n,) = a.shape
    unit = _dotp.SUBLANES * _dotp.LANES
    strip_ = min(strip, max(unit, unit * (n // unit) or unit))
    ap = _pad_to(a, strip_, 0)
    bp = _pad_to(b, strip_, 0)
    return _dotp.dotp(ap, bp, strip=strip_, interpret=(mode == "interpret"))


# ---------------------------------------------------------------------------
# conv2d
# ---------------------------------------------------------------------------

def conv2d(x: jax.Array, w: jax.Array, *, bh: int = 8, bw: int = 128,
           mode: Optional[Mode] = None) -> jax.Array:
    mode = mode or _resolved()
    if mode == "ref":
        return ref.conv2d(x, w).astype(x.dtype)
    n, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    ho, wo = h - kh + 1, wd - kw + 1
    bh_, bw_ = min(bh, ho), min(bw, wo)
    pad_h = (-ho) % bh_
    pad_w = (-wo) % bw_
    xp = jnp.pad(x, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
    out = _conv2d.conv2d(xp, w, bh=bh_, bw=bw_,
                         interpret=(mode == "interpret"))
    return out[:, :ho, :wo, :]


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _blockwise_attention_ref(q, k, v, *, causal, window, scale, bq, bk):
    """Blockwise online-softmax attention in pure jnp (scan over KV strips).

    Same math as the Pallas kernel; memory is O(Sq·bk) instead of O(Sq·Sk),
    so 32k/524k-token cells lower with bounded buffers.  Differentiable.

    Accepts any number of leading (batch/head) dims: (..., S, D).  Keeping
    batch and head as *separate* leading dims matters under GSPMD — a fused
    (B·H) dim sharded over both data and model axes is inexpressible, and
    the partitioner silently replicates the whole attention computation over
    the lane axis (observed 16× FLOP inflation on the 16-lane mesh).
    """
    lead = q.shape[:-2]
    sq, d = q.shape[-2:]
    sk = k.shape[-2]
    scale = scale if scale is not None else d ** -0.5
    bk = min(bk, sk)
    kp = _pad_to(k, bk, -2)
    vp = _pad_to(v, bk, -2)
    skp = kp.shape[-2]
    nkb = skp // bk
    q32 = q.astype(jnp.float32) * scale
    qpos = jnp.arange(sq)[:, None] + (sk - sq)

    ks = jnp.moveaxis(kp.reshape(*lead, nkb, bk, d), -3, 0)
    vs = jnp.moveaxis(vp.reshape(*lead, nkb, bk, d), -3, 0)

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, jb = inp
        s = jnp.einsum("...qd,...kd->...qk", q32, kb.astype(jnp.float32))
        kpos = jb * bk + jnp.arange(bk)[None, :]
        mask = kpos < sk
        if causal:
            mask = mask & (kpos <= qpos)
        if window is not None:
            mask = mask & (kpos > qpos - window)
        s = jnp.where(mask, s, _fa.NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "...qk,...kd->...qd", p, vb.astype(jnp.float32))
        return (m_new, l, acc), None

    init = (jnp.full((*lead, sq), _fa.NEG_INF, jnp.float32),
            jnp.zeros((*lead, sq), jnp.float32),
            jnp.zeros((*lead, sq, d), jnp.float32))
    (m, l, acc), _ = lax.scan(body, init, (ks, vs, jnp.arange(nkb)))
    safe = jnp.where(l > 0, l, 1.0)
    return (acc / safe[..., None]).astype(q.dtype)


# Which CPU/ref attention implementation to lower:
#   "flash" — custom-VJP flash-structured blockwise (triangular causal
#             schedule, O(S·D) residuals) — the §Perf-optimized default.
#   "naive" — autodiff'd blockwise scan (saves per-block f32 trajectories)
#             — the paper-faithful baseline kept for the ablation.
ATTN_IMPL: str = "flash"


def set_attn_impl(impl: str) -> None:
    global ATTN_IMPL
    if impl not in ("flash", "naive"):
        raise ValueError(impl)
    ATTN_IMPL = impl


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              scale: Optional[float] = None, bq: int = 256, bk: int = 512,
              mode: Optional[Mode] = None,
              impl: Optional[str] = None) -> jax.Array:
    """Multi-head attention over (..., S, D) tensors (GQA pre-expanded).

    Leading dims are batch/head; keep them separate (4-D) in distributed
    code so each stays shardable.  The Pallas path folds them into one grid
    axis — safe there, because pallas_call runs on per-device local shapes.

    ``impl``: override ATTN_IMPL per call.  Inference prefill passes
    "naive": with no backward, the kv-outer blockwise scan writes O once,
    while the flash pair-schedule's running O writes amplify (§Perf).
    """
    mode = mode or _resolved()
    impl = impl or ATTN_IMPL
    if mode == "ref":
        # flash needs a *static* window (its block schedule is built at
        # trace time); a traced per-layer window (hymba's scanned schedule)
        # falls back to the naive blockwise path, which masks dynamically.
        static_window = window is None or isinstance(window, int)
        if impl == "flash" and static_window:
            from repro.kernels import flash_ref
            return flash_ref.flash_attention_ref(q, k, v, causal, window,
                                                 scale, bk)
        return _blockwise_attention_ref(q, k, v, causal=causal,
                                        window=window, scale=scale,
                                        bq=bq, bk=bk)
    if q.ndim > 3:   # fold leading dims for the kernel grid
        lead = q.shape[:-2]
        fold = lambda t: t.reshape(-1, *t.shape[-2:])
        out = attention(fold(q), fold(k), fold(v), causal=causal,
                        window=window, scale=scale, bq=bq, bk=bk, mode=mode)
        return out.reshape(*lead, *out.shape[-2:])
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq_, bk_ = min(bq, sq), min(bk, sk)
    # zero-pad both sequence axes to whole blocks (the RVV tail): the kernel
    # predicates off keys past ``kv_len`` and right-aligns the queries on
    # the real lengths, so padding never changes a live row
    out = _fa.flash_attention(_pad_to(q, bq_, 1), _pad_to(k, bk_, 1),
                              _pad_to(v, bk_, 1), causal=causal,
                              window=window, scale=scale, bq=bq_, bk=bk_,
                              kv_len=sk, q_offset=sk - sq,
                              interpret=(mode == "interpret"))
    return out[:, :sq]


# ---------------------------------------------------------------------------
# flash-decode (serving decode step; per-slot length masking)
# ---------------------------------------------------------------------------

def _flash_decode_ref(q, k, v, *, lengths, window, scale, bk,
                      k_scale=None, v_scale=None):
    """Blockwise one-token decode attention in pure jnp.

    q: (B, KVH, G, hd); k/v: (B, S, KVH, hd); lengths: (B,).  Strip-mines
    the KV axis with an online-softmax carry; the per-slot live length is
    applied as tail predication (core.masking.tail_mask) per KV strip —
    the per-row ``vl`` of the serving engine's slot batch.

    ``k_scale``/``v_scale``: optional (B, S, KVH) dequant scales for
    quantized caches; K/V strips widen to f32 in-register and multiply by
    their scale strip (the same fusion the Pallas kernel does).  ``None``
    keeps the unscaled path expression-identical to the pre-format code.
    """
    b, s, kvh, hd = k.shape
    g = q.shape[2]
    scale = scale if scale is not None else hd ** -0.5
    bk = min(bk, s)
    kp = _pad_to(k, bk, 1)
    vp = _pad_to(v, bk, 1)
    nkb = kp.shape[1] // bk
    q32 = q.astype(jnp.float32) * scale

    ks = jnp.moveaxis(kp.reshape(b, nkb, bk, kvh, hd), 1, 0)
    vs = jnp.moveaxis(vp.reshape(b, nkb, bk, kvh, hd), 1, 0)
    scaled = k_scale is not None
    if scaled:
        ksc = jnp.moveaxis(
            _pad_to(k_scale, bk, 1).reshape(b, nkb, bk, kvh), 1, 0)
        vsc = jnp.moveaxis(
            _pad_to(v_scale, bk, 1).reshape(b, nkb, bk, kvh), 1, 0)
    else:
        zeros = jnp.zeros((nkb, b, 0, kvh), jnp.float32)
        ksc = vsc = zeros

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, ksb, vsb, jb = inp
        # live tail of this strip: elements with kpos < lengths  (and inside
        # the sliding window when one is set)
        mask = masking.tail_mask(bk, (lengths - jb * bk)[:, None])  # (B, bk)
        if window is not None:
            kpos = jb * bk + jnp.arange(bk)[None, :]
            mask &= kpos >= (lengths - window)[:, None]
        kw = kb.astype(jnp.float32)
        vw = vb.astype(jnp.float32)
        if scaled:
            kw = kw * ksb[..., None]
            vw = vw * vsb[..., None]
        sc = jnp.einsum("bkgh,bskh->bkgs", q32, kw)
        sc = jnp.where(mask[:, None, None, :], sc, _fd.NEG_INF)
        m_new = jnp.maximum(m, sc.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask[:, None, None, :],
                      jnp.exp(sc - m_new[..., None]), 0.0)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bkgs,bskh->bkgh", p, vw)
        return (m_new, l, acc), None

    init = (jnp.full((b, kvh, g), _fd.NEG_INF, jnp.float32),
            jnp.zeros((b, kvh, g), jnp.float32),
            jnp.zeros((b, kvh, g, hd), jnp.float32))
    (m, l, acc), _ = lax.scan(body, init, (ks, vs, ksc, vsc,
                                           jnp.arange(nkb)))
    safe = jnp.where(l > 0, l, 1.0)
    return (acc / safe[..., None]).astype(q.dtype)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 lengths: Optional[jax.Array] = None,
                 window: Optional[int] = None,
                 scale: Optional[float] = None, bk: int = _fd.DEFAULT_BK,
                 k_scale: Optional[jax.Array] = None,
                 v_scale: Optional[jax.Array] = None,
                 layer: Optional[jax.Array] = None,
                 new_row: Optional[tuple] = None,
                 mode: Optional[Mode] = None) -> jax.Array:
    """One-token decode attention with per-sequence length masking.

    q: (B, H, hd) — the current token's queries; k/v: (B, S, KVH, hd) — the
    KV cache, or with ``layer`` (int32 scalar) the stacked (L, B, S, KVH,
    hd) arena, read at that layer in place; lengths: (B,) int32 count of
    live KV rows per sequence (``None`` = all S live, e.g. enc-dec
    cross-attention).  Returns (B, H, hd).  GQA: consecutive H/KVH query
    heads share a KV head, and each KV row is read once for all of them.

    ``new_row``: the decode step's new token, ``(k_row, v_row)`` of (B, KVH,
    hd) in the cache dtype, plus ``(k_scale, v_scale)`` of (B, KVH) for a
    scaled format — the tuple :func:`repro.models.layers.
    attention_decode_rows` emits.  It sits at key position ``lengths``,
    after the arena rows [0, lengths), and is never written to the arena
    here.

    ``k_scale``/``v_scale``: optional (B, S, KVH) — or stacked (L, B, S,
    KVH) — per-row dequant scales for a quantized cache
    (core/kv_format.py); dequant fuses into the inner loop — the arena is
    never widened in memory.
    """
    b, h, hd = q.shape
    s, kvh = k.shape[-3], k.shape[-2]
    if h % kvh:
        raise ValueError(f"n_heads={h} not divisible by kv_heads={kvh}")
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    lengths = lengths.astype(jnp.int32)
    mode = mode or _resolved()
    if mode == "ref":
        # the oracle attends a layer view with the new row written in
        if layer is not None:
            k, v = k[layer], v[layer]
            if k_scale is not None:
                k_scale, v_scale = k_scale[layer], v_scale[layer]
        if new_row is not None:
            bidx = jnp.arange(b)
            k = k.at[bidx, lengths].set(new_row[0])
            v = v.at[bidx, lengths].set(new_row[1])
            if k_scale is not None:
                k_scale = k_scale.at[bidx, lengths].set(new_row[2])
                v_scale = v_scale.at[bidx, lengths].set(new_row[3])
            lengths = lengths + 1
        out = _flash_decode_ref(q.reshape(b, kvh, h // kvh, hd), k, v,
                                lengths=lengths, window=window,
                                scale=scale, bk=bk,
                                k_scale=k_scale, v_scale=v_scale)
        return out.reshape(b, h, hd)
    if layer is None:
        # a one-layer view of a per-layer cache (a free leading unit axis)
        k, v = k[None], v[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    scales = None if k_scale is None else (k_scale, v_scale)
    rows = None
    if new_row is not None:
        rows = new_row[:2]
        if k_scale is not None:
            rows = tuple(r.astype(jnp.float32) * sc[..., None]
                         for r, sc in zip(rows, new_row[2:]))
    window_lo = None
    if window is not None:
        window_lo = lengths + (new_row is not None) - window
    return _fd.flash_decode(q, k, v, lengths, layer, rows=rows,
                            window_lo=window_lo, scale=scale, bk=bk,
                            scales=scales, interpret=(mode == "interpret"))


# ---------------------------------------------------------------------------
# flash-prefill-chunk (chunked prompt ingestion; dynamic causal boundary)
# ---------------------------------------------------------------------------

def _flash_prefill_chunk_ref(q, k, v, *, prefix, window, scale, bk,
                             k_scale=None, v_scale=None):
    """Blockwise chunk-append attention in pure jnp.

    q: (B, KVH, G, C, hd); k/v: (B, S, KVH, hd); prefix: (B,) rows live
    before the chunk (the chunk's own K/V sit at rows [prefix, prefix+C)).
    Strip-mines the KV axis with an online-softmax carry; each chunk query
    at position prefix + i attends kpos <= prefix + i — causal within the
    chunk, full over the already-written prefix.

    ``k_scale``/``v_scale``: optional (B, S, KVH) dequant scales — same
    in-register widening contract as :func:`_flash_decode_ref`.
    """
    b, s, kvh, hd = k.shape
    g, c = q.shape[2], q.shape[3]
    scale = scale if scale is not None else hd ** -0.5
    bk = min(bk, s)
    kp = _pad_to(k, bk, 1)
    vp = _pad_to(v, bk, 1)
    nkb = kp.shape[1] // bk
    q32 = q.astype(jnp.float32) * scale
    qpos = prefix[:, None] + jnp.arange(c)[None, :]        # (B, C)

    ks = jnp.moveaxis(kp.reshape(b, nkb, bk, kvh, hd), 1, 0)
    vs = jnp.moveaxis(vp.reshape(b, nkb, bk, kvh, hd), 1, 0)
    scaled = k_scale is not None
    if scaled:
        ksc = jnp.moveaxis(
            _pad_to(k_scale, bk, 1).reshape(b, nkb, bk, kvh), 1, 0)
        vsc = jnp.moveaxis(
            _pad_to(v_scale, bk, 1).reshape(b, nkb, bk, kvh), 1, 0)
    else:
        ksc = vsc = jnp.zeros((nkb, b, 0, kvh), jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, ksb, vsb, jb = inp
        kpos = jb * bk + jnp.arange(bk)[None, :]           # (1, bk)
        mask = kpos[:, None, :] <= qpos[..., None]         # (B, C, bk)
        if window is not None:
            mask &= kpos[:, None, :] > (qpos[..., None] - window)
        kw = kb.astype(jnp.float32)
        vw = vb.astype(jnp.float32)
        if scaled:
            kw = kw * ksb[..., None]
            vw = vw * vsb[..., None]
        sc = jnp.einsum("bkgch,bskh->bkgcs", q32, kw)
        sc = jnp.where(mask[:, None, None], sc, _fpc.NEG_INF)
        m_new = jnp.maximum(m, sc.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask[:, None, None],
                      jnp.exp(sc - m_new[..., None]), 0.0)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bkgcs,bskh->bkgch", p, vw)
        return (m_new, l, acc), None

    init = (jnp.full((b, kvh, g, c), _fpc.NEG_INF, jnp.float32),
            jnp.zeros((b, kvh, g, c), jnp.float32),
            jnp.zeros((b, kvh, g, c, hd), jnp.float32))
    (m, l, acc), _ = lax.scan(body, init, (ks, vs, ksc, vsc,
                                           jnp.arange(nkb)))
    safe = jnp.where(l > 0, l, 1.0)
    return (acc / safe[..., None]).astype(q.dtype)


def flash_prefill_chunk(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        prefix: jax.Array, window: Optional[int] = None,
                        scale: Optional[float] = None, bk: int = 512,
                        k_scale: Optional[jax.Array] = None,
                        v_scale: Optional[jax.Array] = None,
                        mode: Optional[Mode] = None) -> jax.Array:
    """Chunk-append prefill attention with a dynamic causal boundary.

    q: (B, C, H, hd) — one prompt chunk's queries; k/v: (B, S, KVH, hd) —
    the cache arena, with the chunk's K/V already written at rows
    [prefix, prefix + C); prefix: (B,) int32 rows live *before* the chunk.
    Returns (B, C, H, hd).  ``prefix`` is runtime data (SMEM scalar in the
    kernel), so every chunk of every prompt position reuses one compiled
    shape — the whole point of stripmined prefill.  GQA is handled here:
    H is grouped onto KVH so each KV head is read once per chunk.

    ``k_scale``/``v_scale``: optional (B, S, KVH) per-row dequant scales
    for a quantized cache (core/kv_format.py); dequant fuses into the
    inner loop — the arena is never widened in memory.
    """
    b, c, h, hd = q.shape
    _, s, kvh, _ = k.shape
    if h % kvh:
        raise ValueError(f"n_heads={h} not divisible by kv_heads={kvh}")
    g = h // kvh
    # (B, C, H, hd) -> (B, KVH, G, C, hd): consecutive G heads share a KV head
    qg = q.transpose(0, 2, 1, 3).reshape(b, kvh, g, c, hd)
    prefix = prefix.astype(jnp.int32)
    mode = mode or _resolved()
    if mode == "ref":
        out = _flash_prefill_chunk_ref(qg, k, v, prefix=prefix,
                                       window=window, scale=scale, bk=bk,
                                       k_scale=k_scale, v_scale=v_scale)
        return out.reshape(b, h, c, hd).transpose(0, 2, 1, 3)
    bk_ = min(bk, s)
    kp = _pad_to(k, bk_, 1)
    vp = _pad_to(v, bk_, 1)
    # fold (B, KVH) into the kernel grid axis; padded rows sit beyond every
    # live length, so the causal/tail mask drops them
    kf = jnp.moveaxis(kp, 2, 1).reshape(b * kvh, kp.shape[1], hd)
    vf = jnp.moveaxis(vp, 2, 1).reshape(b * kvh, vp.shape[1], hd)
    qf = qg.reshape(b * kvh, g, c, hd)
    pf = jnp.repeat(prefix, kvh)
    scales = None
    if k_scale is not None:
        ksf = jnp.moveaxis(_pad_to(k_scale, bk_, 1), 2, 1).reshape(
            b * kvh, kp.shape[1])
        vsf = jnp.moveaxis(_pad_to(v_scale, bk_, 1), 2, 1).reshape(
            b * kvh, vp.shape[1])
        scales = (ksf, vsf)
    out = _fpc.flash_prefill_chunk(qf, kf, vf, pf, window=window,
                                   scale=scale, bk=bk_, scales=scales,
                                   interpret=(mode == "interpret"))
    out = out.reshape(b, kvh, g, c, hd).reshape(b, h, c, hd)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# SSD (Mamba2)
# ---------------------------------------------------------------------------

def _chunked_ssd_ref(x, log_a, B, C, *, chunk, initial_state=None):
    """Chunked SSD in pure jnp (scan over chunks) — same schedule as the
    Pallas kernel, differentiable, bounded memory for 500k sequences."""
    bh, s, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = _pad_to(x, chunk, 1)
        log_a = _pad_to(log_a, chunk, 1)   # log_a=0 => decay 1, harmless
        B = _pad_to(B, chunk, 1)
        C = _pad_to(C, chunk, 1)
    sp = x.shape[1]
    nc = sp // chunk

    xc = jnp.moveaxis(x.reshape(bh, nc, chunk, p).astype(jnp.float32), 1, 0)
    lac = jnp.moveaxis(log_a.reshape(bh, nc, chunk).astype(jnp.float32), 1, 0)
    Bc = jnp.moveaxis(B.reshape(bh, nc, chunk, n).astype(jnp.float32), 1, 0)
    Cc = jnp.moveaxis(C.reshape(bh, nc, chunk, n).astype(jnp.float32), 1, 0)

    ii = jnp.arange(chunk)[:, None]
    jj = jnp.arange(chunk)[None, :]

    def body(state, inp):
        xb, lab, Bb, Cb = inp
        cum = jnp.cumsum(lab, axis=-1)                       # (bh, Q)
        total = cum[:, -1]
        seg = cum[:, :, None] - cum[:, None, :]
        seg = jnp.where(ii >= jj, seg, _fa.NEG_INF)
        scores = jnp.einsum("bin,bjn->bij", Cb, Bb) * jnp.exp(seg)
        y = jnp.einsum("bij,bjp->bip", scores, xb)
        y += jnp.einsum("bin,bnp->bip", Cb * jnp.exp(cum)[..., None], state)
        w = jnp.exp(total[:, None] - cum)[..., None] * Bb     # (bh, Q, N)
        state = (jnp.exp(total)[:, None, None] * state
                 + jnp.einsum("bjn,bjp->bnp", w, xb))
        return state, y

    st0 = (jnp.zeros((bh, n, p), jnp.float32) if initial_state is None
           else initial_state.astype(jnp.float32))
    final, ys = lax.scan(body, st0, (xc, lac, Bc, Cc))
    y = jnp.moveaxis(ys, 0, 1).reshape(bh, sp, p)[:, :s]
    return y.astype(x.dtype), final


def ssd(x: jax.Array, log_a: jax.Array, B: jax.Array, C: jax.Array, *,
        chunk: int = 256, initial_state: Optional[jax.Array] = None,
        mode: Optional[Mode] = None):
    """Chunked SSD: x (BH,S,P), log_a (BH,S), B/C (BH,S,N) -> (y, state).

    ``initial_state`` (BH, N, P) seeds the recurrence (serving's chunked
    prefill threads it across prompt chunks); supported by every path —
    the Pallas kernel takes it as a VMEM-seeded operand, and ragged
    lengths are zero-padded to whole chunks, so SSM prefill never falls
    back to the jnp path on TPU."""
    mode = mode or _resolved()
    if mode == "ref":
        return _chunked_ssd_ref(x, log_a, B, C, chunk=chunk,
                                initial_state=initial_state)
    s = x.shape[1]
    chunk_ = min(chunk, s)
    if s % chunk_:
        # zero-pad to whole chunks: log_a = 0 (decay 1) with x = B = 0 adds
        # nothing to the carried state, and the padded y rows are dropped
        x, log_a, B, C = (_pad_to(t, chunk_, 1) for t in (x, log_a, B, C))
    y, st = _ssd.ssd(x, log_a, B, C, chunk=chunk_,
                     initial_state=initial_state,
                     interpret=(mode == "interpret"))
    return y[:, :s], st


def ssd_decode_step(x_t, log_a_t, B_t, C_t, state):
    """Single-token SSD recurrence for serving: O(N·P) per head per step.

    x_t: (BH, P), log_a_t: (BH,), B_t/C_t: (BH, N), state: (BH, N, P).
    """
    state = (jnp.exp(log_a_t.astype(jnp.float32))[:, None, None] * state
             + B_t.astype(jnp.float32)[:, :, None]
             * x_t.astype(jnp.float32)[:, None, :])
    y = jnp.einsum("bn,bnp->bp", C_t.astype(jnp.float32), state)
    return y.astype(x_t.dtype), state
