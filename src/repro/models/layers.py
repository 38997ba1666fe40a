"""Shared transformer layers: norms, RoPE, GQA attention, MLPs.

Pure-function style: parameters are nested dicts of jnp arrays, every layer
is ``fn(params, cfg, x, ...) -> y``.  Matmuls accumulate in f32 and cast
back to the activation dtype (cfg.act_dtype).  Sharding is expressed through
``core.lanes`` logical-axis constraints so the same code runs on 1-device
CPU tests and on the production mesh.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name
from jax.sharding import AxisType

from repro.core import kv_format as kvf, lanes
from repro.kernels import ops

RULES = lanes.LogicalRules()

# Decode-position sentinel for a slot whose prompt is mid-chunked-prefill.
# The serving engine parks the slot's position pointer here so in-flight
# decode steps cannot touch the slot's freshly written rows: KV scatters at
# PARKED_POS go out of bounds and are dropped (XLA scatter semantics), and
# recurrent-state writes (SSD state / conv tail, which are not
# position-addressed) mask on ``pos < PARKED_POS`` — see the families'
# ``rows_scatter`` implementations.  Well inside int32 so ``pos + 1`` (the
# sampling key fold, flash-decode lengths) never overflows.
PARKED_POS: int = 1 << 30


def _dot(x, w, adtype):
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(adtype)


# TP-boundary reduction lowering (§Perf iterations 4-5):
#   "auto"         — GSPMD decides; boundary dots keep f32 partials, so the
#                    lane all-reduce moves f32 (baseline).
#   "bf16_dot"     — boundary dots emit 16-bit partials (XLA still
#                    accumulates the within-chip contraction in f32), so
#                    GSPMD's all-reduce and every backward cotangent
#                    collective at the boundary moves 16-bit — half the
#                    wire, same schedule (it5, CONFIRMED).
#   "bf16_scatter" — explicit nested shard_map: local partial matmul →
#                    16-bit psum_scatter over the sequence dim.  On paper
#                    4× less wire; in practice the shard_map boundary
#                    blocks GSPMD propagation and the surrounding gathers
#                    blow up (it4, REFUTED — kept for the record).
TP_REDUCE: str = "auto"


def set_tp_reduce(mode: str) -> None:
    global TP_REDUCE
    if mode not in ("auto", "bf16_dot", "bf16_scatter"):
        raise ValueError(mode)
    TP_REDUCE = mode


def tp_boundary_dot(h, w, adtype, rules):
    """Lane-contracted projection at a TP boundary: out = h @ w, with the
    contraction dim lane-sharded.  Output is seq_tp-sharded (or replicated
    when seq_tp is off / no lane axis is present)."""
    mesh = jax.sharding.get_abstract_mesh()
    use_explicit = (
        TP_REDUCE == "bf16_scatter"
        and h.ndim == 3
        and not mesh.empty
        and lanes.LANE_AXIS in mesh.axis_names
        and mesh.shape[lanes.LANE_AXIS] > 1
        and h.shape[1] % mesh.shape[lanes.LANE_AXIS] == 0
        and h.shape[-1] % mesh.shape[lanes.LANE_AXIS] == 0
        and mesh.axis_types[mesh.axis_names.index(lanes.LANE_AXIS)]
        != AxisType.Manual)
    if not use_explicit:
        seq_ax = "seq_tp" if h.ndim == 3 else None
        if TP_REDUCE == "bf16_dot":
            # 16-bit partials: the lane psum and its bwd move 2 B/elem
            out = jnp.dot(h, w, preferred_element_type=adtype)
            return lanes.constrain(out, rules, "batch", seq_ax, "embed")
        # constrain AFTER the cast: the sharding-change point (where GSPMD
        # inserts bwd cotangent collectives) is then 16-bit, not f32 (it6)
        out = jnp.dot(h, w,
                      preferred_element_type=jnp.float32).astype(adtype)
        return lanes.constrain(out, rules, "batch", seq_ax, "embed")

    from jax.sharding import PartitionSpec as P

    # 16-bit wire dtype.  On TPU this is bf16; the CPU XLA backend
    # miscompiles bf16 tiled collectives ("invalid binary opcode copy"),
    # so the CPU validation/dry-run path uses IEEE f16 — same wire bytes.
    wire_dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float16

    def body(h_loc, w_loc):
        part = jnp.dot(h_loc, w_loc,
                       preferred_element_type=jnp.float32).astype(wire_dt)
        out = jax.lax.psum_scatter(part, lanes.LANE_AXIS,
                                   scatter_dimension=1, tiled=True)
        return out.astype(adtype)

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, lanes.LANE_AXIS), P(lanes.LANE_AXIS, None)),
        out_specs=P(None, lanes.LANE_AXIS, None),
        axis_names={lanes.LANE_AXIS}, check_vma=False)(h, w)
    return lanes.constrain(out, rules, "batch", "seq_tp", "embed")


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd), positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                         # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, optional qk_norm / sliding window)
# ---------------------------------------------------------------------------

def attention_init(key, cfg, dtype) -> dict:
    d, hd = cfg.d_model, cfg.hd
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": (jax.random.normal(kq, (d, cfg.n_heads * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(kk, (d, cfg.n_kv_heads * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(kv, (d, cfg.n_kv_heads * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ko, (cfg.n_heads * hd, d))
               * (cfg.n_heads * hd) ** -0.5).astype(dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _project_qkv(p, cfg, x, positions, rules):
    b, s, d = x.shape
    hd, nh, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    adt = cfg.adtype
    q = _dot(x, p["wq"], adt).reshape(b, s, nh, hd)
    k = _dot(x, p["wk"], adt).reshape(b, s, nkv, hd)
    v = _dot(x, p["wv"], adt).reshape(b, s, nkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.rms_eps)
        k = rmsnorm(p["k_norm"], k, cfg.rms_eps)
    if positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = lanes.constrain(q, rules, "batch", None, "heads", None)
    # k/v deliberately unconstrained here: the training/prefill consumer is
    # the GQA head-expansion (16-way "heads"); the decode cache write is
    # "kv_heads"-sharded.  Constraining both directions here would force a
    # reshard (see attention() below); GSPMD propagates from the consumer.
    return q, k, v


def attention(p: dict, cfg, x: jax.Array, *, positions: jax.Array,
              causal: bool = True, window: Optional[int] = None,
              rules=RULES, kv: Optional[tuple] = None) -> jax.Array:
    """Full-sequence attention (train/prefill). x: (B, S, d).

    ``kv``: optional externally-computed (k, v) with their own positions —
    used for enc-dec cross-attention (then ``causal=False``).
    """
    b, s, d = x.shape
    hd, nh, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    q, k, v = (None, None, None)
    if kv is None:
        q, k, v = _project_qkv(p, cfg, x, positions, rules)
    else:
        adt = cfg.adtype
        q = _dot(x, p["wq"], adt).reshape(b, s, nh, hd)
        if cfg.qk_norm:
            q = rmsnorm(p["q_norm"], q, cfg.rms_eps)
        if positions is not None:
            q = rope(q, positions, cfg.rope_theta)
        k, v = kv
    group = nh // nkv
    sk = k.shape[1]
    # Expand KV heads to query heads (GQA), then move heads to a *separate*
    # leading axis, constrained to the lane axis.  Two GSPMD pitfalls are
    # avoided here (both observed as ~lane-count× FLOP inflation in the
    # dry-run HLO): (1) constraining the unexpanded KV (nkv < lanes) forces
    # an 8→16-way reshard = involuntary full rematerialization; (2) folding
    # (B·H) into one dim makes the data×model product sharding
    # inexpressible, so the partitioner replicates attention over lanes.
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    qf = q.transpose(0, 2, 1, 3)                 # (B, H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3)
    vf = v.transpose(0, 2, 1, 3)
    qf = lanes.constrain(qf, rules, "batch", "heads", None, None)
    kf = lanes.constrain(kf, rules, "batch", "heads", None, None)
    vf = lanes.constrain(vf, rules, "batch", "heads", None, None)
    of = ops.attention(qf, kf, vf, causal=causal, window=window)
    of = lanes.constrain(of, rules, "batch", "heads", None, None)
    o = of.transpose(0, 2, 1, 3)
    out = tp_boundary_dot(o.reshape(b, s, nh * hd), p["wo"], cfg.adtype,
                          rules)
    # named so the "save_tp" remat policy can keep exactly the TP-boundary
    # activations (post-reduce, bf16, seq-sharded under SP) and skip
    # replaying the per-layer collectives during backward recompute
    return checkpoint_name(out, "tp_boundary")


def _decode_qkv(p: dict, cfg, x_t: jax.Array, pos: jax.Array,
                use_rope: bool) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The decode step's QKV projection (+ qk_norm / RoPE at ``pos``).
    x_t: (B, d).  Returns q (B, 1, H, hd), k_t/v_t (B, 1, KVH, hd)."""
    b, d = x_t.shape
    hd, nh, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    adt = cfg.adtype
    q = _dot(x_t, p["wq"], adt).reshape(b, 1, nh, hd)
    k_t = _dot(x_t, p["wk"], adt).reshape(b, 1, nkv, hd)
    v_t = _dot(x_t, p["wv"], adt).reshape(b, 1, nkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.rms_eps)
        k_t = rmsnorm(p["k_norm"], k_t, cfg.rms_eps)
    if use_rope:
        q = rope(q, pos[:, None], cfg.rope_theta)
        k_t = rope(k_t, pos[:, None], cfg.rope_theta)
    return q, k_t, v_t


def attention_decode(p: dict, cfg, x_t: jax.Array, cache: dict,
                     pos: jax.Array, *, window: Optional[int] = None,
                     layer_kv: Optional[tuple] = None, use_rope: bool = True,
                     rules=RULES) -> tuple[jax.Array, dict]:
    """One decode step. x_t: (B, d); pos: (B,) next position per sample.

    ``cache``: {"k": (B, Smax, KVH, hd), "v": ...} — updated functionally.
    ``layer_kv``: static cross-attention KV (enc-dec) — cache unused then.
    """
    b, d = x_t.shape
    hd, nh, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    adt = cfg.adtype
    if layer_kv is None:
        q, k_t, v_t = _decode_qkv(p, cfg, x_t, pos, use_rope)
        # scatter the new KV at per-sample positions
        bidx = jnp.arange(b)
        ck = cache["k"].at[bidx, pos].set(k_t[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[bidx, pos].set(v_t[:, 0].astype(cache["v"].dtype))
        cache = {"k": ck, "v": cv}
        k_all, v_all = ck, cv
        kv_len_mask_pos = pos
    else:
        # cross-attention: no RoPE on q (positions belong to the static KV)
        q = _dot(x_t, p["wq"], adt).reshape(b, 1, nh, hd)
        if cfg.qk_norm:
            q = rmsnorm(p["q_norm"], q, cfg.rms_eps)
        k_all, v_all = layer_kv
        kv_len_mask_pos = None
    # flash-decode over the (kv_seq lane-sharded) cache: each lane attends
    # its KV slice, the online-softmax combine is the tiny cross-lane
    # reduction (C4 applied to attention — see core/lanes.py "kv_seq")
    k_all = lanes.constrain(k_all, rules, "batch", "kv_seq", None, None)
    v_all = lanes.constrain(v_all, rules, "batch", "kv_seq", None, None)
    # live cache length per sample = pos+1 (the slot's vl); None for static
    # cross-attention KV, which attends everything
    lengths = None if kv_len_mask_pos is None else kv_len_mask_pos + 1
    o = ops.flash_decode(
        q[:, 0], k_all, v_all, lengths=lengths,
        window=window if kv_len_mask_pos is not None else None)
    out = _dot(o.reshape(b, nh * hd), p["wo"], adt)
    return out, cache


def attention_chunk(p: dict, cfg, x: jax.Array, slot_kv: dict,
                    positions: jax.Array, start: jax.Array, *,
                    window: Optional[int] = None,
                    rules=RULES) -> tuple[jax.Array, tuple]:
    """One prompt chunk: attend the slot's prefix + the chunk, return the
    chunk's K/V rows for the caller's arena splice.

    x: (B, C, d) chunk hidden states; ``slot_kv``: the slot's cache *view*
    {"k","v"} of (B, Smax, KVH, hd) — rows [0, start) are live, the rest
    stale.  The chunk's K/V are patched into a temporary copy of the view
    for attention; the **arena itself is not written here** — the driver
    splices all layers' chunk rows with one in-place dynamic-update-slice,
    so the bytes written per chunk stay O(chunk rows), not O(slot) or
    O(arena).  ``positions`` are absolute (start + arange(C)) so RoPE
    matches monolithic prefill; ``start`` is traced, so every chunk
    position reuses one compiled shape.  Returns (out, (k_rows, v_rows)),
    rows shaped (B, C, KVH, hd) in the cache dtype; when ``slot_kv`` is a
    scaled-format view (carries ``k_scale``/``v_scale`` leaves) the rows
    are quantized on write and the return is (out, (k_rows, v_rows,
    k_scales, v_scales)) with scales shaped (B, C, KVH) f32.
    """
    b, c, d = x.shape
    q, k, v = _project_qkv(p, cfg, x, positions, rules)
    scaled = "k_scale" in slot_kv
    if scaled:
        fmt = kvf.get(kv_cache_format(slot_kv))
        k_rows, k_scales = kvf.quantize(fmt, k)
        v_rows, v_scales = kvf.quantize(fmt, v)
    else:
        k_rows = k.astype(slot_kv["k"].dtype)
        v_rows = v.astype(slot_kv["v"].dtype)
    # Scatter, not dynamic_update_slice: a speculative verify chunk may
    # overrun the slot's last rows (start + C > Smax), and DUS would CLAMP
    # the start so the window fits — shifting every patched row down and
    # corrupting the view's committed prefix.  Scatter drops the overflow
    # rows instead and lands each in-bounds row at its true position, so
    # every draw a request can still commit (q-pos < Smax) stays bit-exact.
    rows_idx = start + jnp.arange(c)
    ck = slot_kv["k"].at[:, rows_idx].set(k_rows)
    cv = slot_kv["v"].at[:, rows_idx].set(v_rows)
    prefix = jnp.full((b,), start, jnp.int32)
    if scaled:
        cks = slot_kv["k_scale"].at[:, rows_idx].set(k_scales)
        cvs = slot_kv["v_scale"].at[:, rows_idx].set(v_scales)
        o = ops.flash_prefill_chunk(q, ck, cv, prefix=prefix, window=window,
                                    k_scale=cks, v_scale=cvs)
        out = _dot(o.reshape(b, c, -1), p["wo"], cfg.adtype)
        return out, (k_rows, v_rows, k_scales, v_scales)
    o = ops.flash_prefill_chunk(q, ck, cv, prefix=prefix, window=window)
    out = _dot(o.reshape(b, c, -1), p["wo"], cfg.adtype)
    return out, (k_rows, v_rows)


def attention_decode_rows(p: dict, cfg, x_t: jax.Array, kv: dict,
                          li: jax.Array, pos: jax.Array, *,
                          window: Optional[int] = None,
                          rules=RULES) -> tuple[jax.Array, tuple]:
    """One decode step of layer ``li`` against the read-only stacked arena,
    returning the new K/V rows instead of a rewritten cache.

    The generic :func:`attention_decode` scatters into its cache argument
    and returns the whole updated layer cache; threading that through a
    layer scan re-materialises the full arena every step.  Here flash-decode
    reads layer ``li`` of the stacked arena where it lies and takes the new
    token's row as an operand of its own; the caller (the arena driver)
    collects the rows of every layer and writes them into the resident
    arena with one in-place scatter.  x_t: (B, d); kv: {"k","v"} of (L, B,
    Smax, KVH, hd); li: int32 scalar.  Returns (out, (k_row, v_row)) with
    rows shaped (B, KVH, hd); scaled-format arenas quantize on write and
    return (out, (k_row, v_row, k_scale, v_scale)) with scales shaped
    (B, KVH) — attention sees the row as later steps will read it back.

    A slot attends arena rows [0, pos) and its new row at ``pos``.  A slot
    whose ``pos`` lies outside the arena (``PARKED_POS``, mid-chunked-
    prefill or idle) has no live arena rows: its output is discarded and
    its row write dropped, so it attends the new row alone.
    """
    b, d = x_t.shape
    nh, hd = cfg.n_heads, cfg.hd
    q, k_t, v_t = _decode_qkv(p, cfg, x_t, pos, True)
    if "k_scale" in kv:
        fmt = kvf.get(kv_cache_format(kv))
        k_row, k_sc = kvf.quantize(fmt, k_t[:, 0])
        v_row, v_sc = kvf.quantize(fmt, v_t[:, 0])
        rows = (k_row, v_row, k_sc, v_sc)
    else:
        rows = (k_t[:, 0].astype(kv["k"].dtype),
                v_t[:, 0].astype(kv["v"].dtype))
    k_all = lanes.constrain(kv["k"], rules, None, "batch", "kv_seq", None,
                            None)
    v_all = lanes.constrain(kv["v"], rules, None, "batch", "kv_seq", None,
                            None)
    live = jnp.where(pos < k_all.shape[2], pos, 0)
    o = ops.flash_decode(q[:, 0], k_all, v_all, lengths=live, layer=li,
                         new_row=rows, window=window,
                         k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"))
    out = _dot(o.reshape(b, nh * hd), p["wo"], cfg.adtype)
    return out, rows


def init_kv_cache(cfg, batch: int, max_seq: int, dtype=None,
                  kv_format: str = "fp32") -> dict:
    """Per-layer KV cache in a storage format (core/kv_format.py).

    ``fp32`` (the default) stores at ``dtype or cfg.adtype`` — structurally
    and bit-wise identical to the pre-format cache.  Scaled formats (int8,
    fp8) add ``k_scale``/``v_scale`` sidecar leaves of (batch, max_seq,
    KVH) f32, initialised to 1.0 so dequant of never-written rows is exact
    zero (matching the zero-initialised reference arena).
    """
    fmt = kvf.get(kv_format)
    if fmt.store_dtype is None:
        dtype = dtype or cfg.adtype
    else:
        dtype = fmt.store_dtype
    cache = {
        "k": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.hd), dtype),
    }
    if fmt.scaled:
        ones = jnp.ones((batch, max_seq, cfg.n_kv_heads), kvf.SCALE_DTYPE)
        cache["k_scale"] = ones
        cache["v_scale"] = ones
    return cache


def layer_view(cache, li):
    """Layer ``li``'s slice of a stacked cache pytree (leaf dim 0)."""
    return jax.tree.map(lambda leaf: leaf[li], cache)


def kv_cache_format(cache: dict) -> str:
    """Recover the storage format of a (per-layer or stacked) KV cache
    pytree from its structure/dtype — the leaves, not a side channel, are
    the source of truth, so views/forks/donated generations can't drift."""
    k = cache["k"]
    if "k_scale" in cache:
        return "int8" if k.dtype == jnp.int8 else "fp8"
    if k.dtype == jnp.bfloat16:
        return "bf16"
    return "fp32"


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, d_ff: int, act: str, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = d ** -0.5, d_ff ** -0.5
    p = {
        "w_up": (jax.random.normal(k1, (d, d_ff)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k2, (d_ff, d)) * s_out).astype(dtype),
    }
    if act == "silu_gated":
        p["w_gate"] = (jax.random.normal(k3, (d, d_ff)) * s_in).astype(dtype)
    return p


def mlp(p: dict, cfg, x: jax.Array, *, act: Optional[str] = None,
        rules=RULES) -> jax.Array:
    act = act or cfg.act
    adt = cfg.adtype
    mid = (None,) * (x.ndim - 2)     # rank-agnostic: (B,S,d) or (B,d)
    up = _dot(x, p["w_up"], adt)
    up = lanes.constrain(up, rules, "batch", *mid, "ffn")
    if act == "silu_gated":
        gate = _dot(x, p["w_gate"], adt)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(adt) * up
    elif act == "relu2":
        r = jax.nn.relu(up.astype(jnp.float32))
        h = (r * r).astype(adt)
    elif act == "gelu":
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(adt)
    else:
        raise ValueError(f"unknown act {act!r}")
    if x.ndim == 3:
        out = tp_boundary_dot(h, p["w_down"], adt, rules)
        return checkpoint_name(out, "tp_boundary")
    out32 = jnp.dot(h, p["w_down"], preferred_element_type=jnp.float32)
    out32 = lanes.constrain(out32, rules, "batch", *mid, "embed")
    return out32.astype(adt)


# ---------------------------------------------------------------------------
# embeddings / LM head / losses
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d)) * d ** -0.5).astype(dtype)


def embed_lookup(table: jax.Array, tokens: jax.Array, rules=RULES) -> jax.Array:
    out = table[tokens]
    ax = "seq_tp" if tokens.ndim >= 2 and tokens.shape[-1] > 1 else None
    return lanes.constrain(out, rules, "batch", ax, "embed")


def lm_head_logits(w: jax.Array, x: jax.Array, rules=RULES) -> jax.Array:
    logits = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return lanes.constrain(logits, rules, "batch", None, "vocab_tp")


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token CE. logits (B,S,V) f32, labels (B,S) int32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


def blockwise_cross_entropy(w_head: jax.Array, x: jax.Array,
                            labels: jax.Array,
                            mask: Optional[jax.Array] = None, *,
                            block: int = 512, rules=RULES) -> jax.Array:
    """CE fused with the LM head, scanned over sequence blocks.

    Never materialises the (B, S, V) logits tensor — the LM-head matmul of
    each block chains directly into its logsumexp reduction (C5 chaining at
    the loss level).  This is the default for large-vocab configs.
    """
    b, s, d = x.shape
    pad = (-s) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask_full = jnp.pad(
            mask if mask is not None else jnp.ones((b, s), jnp.float32),
            ((0, 0), (0, pad)))
    else:
        mask_full = mask if mask is not None else jnp.ones((b, s), jnp.float32)
    sp = x.shape[1]
    nb = sp // block
    xb = jnp.moveaxis(x.reshape(b, nb, block, d), 1, 0)
    lb = jnp.moveaxis(labels.reshape(b, nb, block), 1, 0)
    mb = jnp.moveaxis(mask_full.reshape(b, nb, block), 1, 0)

    def body(carry, inp):
        nll_sum, cnt = carry
        xc, lc, mc = inp
        logits = jnp.dot(xc, w_head, preferred_element_type=jnp.float32)
        logits = lanes.constrain(logits, rules, "batch", None, "vocab_tp")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mc
        return (nll_sum + nll.sum(), cnt + mc.sum()), None

    (nll_sum, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xb, lb, mb))
    return nll_sum / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# stochastic sampling (temperature / top-k / top-p / min-p)
# ---------------------------------------------------------------------------
#
# The serving analogue of the paper's lane discipline: per-slot PRNG "state"
# never leaves the lane because there is no state to move — a slot's key for
# the token at absolute cache position q is fold_in(fold_in(key0, seed), q),
# a pure function of the request's seed and q.  Nothing random rides the
# donated arena or the scan carry, so a slot's token stream is independent
# of batch composition, chunked-prefill interleaving, preemption/recompute
# (the replay revisits the same positions) and donation generation.

def _monotone_key(x: jax.Array) -> jax.Array:
    """Order-preserving bijection f32 -> uint32 (the IEEE-754 total-order
    trick: flip the sign bit of non-negatives, all bits of negatives).
    Callers canonicalise -0.0 to +0.0 first (``x + 0.0``)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where((u >> 31) == 0, u ^ jnp.uint32(0x80000000), ~u)


def masked_logits(logits: jax.Array, temp: jax.Array, top_k: jax.Array,
                  top_p: jax.Array, min_p: jax.Array) -> jax.Array:
    """Temperature-scale + mask logits per slot (all vectorized over B).

    logits: (B, V); temp/top_p/min_p: (B,) f32; top_k: (B,) i32.  Order of
    operations per slot: divide by temperature, then intersect the top-k,
    nucleus (top-p) and min-p keep-sets computed on the *scaled*
    distribution; masked-out entries become -inf.  Conventions:

      * top_k <= 0 disables the top-k filter (ties at the k-th logit are
        all kept);
      * top-p keeps the smallest descending-prob prefix whose mass is
        >= top_p — an entry ``v`` survives iff the probability mass
        strictly above it is < top_p (the exclusive-cumulative-mass rule,
        expressed value-wise);
      * min_p drops entries whose probability is < min_p * max-prob;
      * the argmax entry always survives, so the kept set is never empty.

    All three filters are value thresholds, so the mask reduces to one
    compare against ``max(top-k cutoff, nucleus cutoff, min-p cutoff)``.
    The two order-statistic cutoffs are found by *exact bit-bisection* on
    the monotone uint32 image of the scaled logits (32 fused halvings of
    count{x >= t} / mass{x > t}) instead of a full descending sort —
    XLA's comparator sort costs ~400 us at (4, 512) on CPU where the dual
    bisection costs ~40 us, and the gap widens with vocab; the kept set
    is bit-identical to the sort formulation.
    """
    v = logits.shape[-1]
    b = logits.shape[0]
    x = logits.astype(jnp.float32) / jnp.maximum(temp, 1e-6)[:, None]
    x = x + 0.0                          # -0.0 -> +0.0 for the key map
    keys = _monotone_key(x)              # (B, V) uint32, order of x
    top = jnp.max(x, axis=-1, keepdims=True)
    w = jnp.exp(x - top)                 # unnormalised probs
    z = w.sum(axis=-1)                   # (B,)
    k = jnp.clip(top_k, 1, v).astype(jnp.uint32)
    pz = top_p * z                       # compare mass*Z < p*Z: no divide

    def body(_, st):
        lo_k, hi_k, lo_p, hi_p = st
        # top-k: largest t with count{x >= t} >= k  (== the k-th largest
        # value, ties included by the final >= compare)
        mid = lo_k + (hi_k - lo_k) // 2
        cnt = (keys >= mid[:, None]).sum(axis=-1).astype(jnp.uint32)
        ok = cnt >= k
        lo_k = jnp.where(ok, mid, lo_k)
        hi_k = jnp.where(ok, hi_k, mid)
        # top-p: smallest t with mass{x > t} < p  (strictly-above mass)
        mid = lo_p + (hi_p - lo_p) // 2
        mass = jnp.where(keys > mid[:, None], w, 0.0).sum(axis=-1)
        ok = mass < pz
        hi_p = jnp.where(ok, mid, hi_p)
        lo_p = jnp.where(ok, lo_p, mid)
        return lo_k, hi_k, lo_p, hi_p

    zero = jnp.zeros((b,), jnp.uint32)
    full = jnp.full((b,), 0xFFFFFFFF, jnp.uint32)
    lo_k, _, _, hi_p = jax.lax.fori_loop(0, 32, body,
                                         (zero, full, zero, full))
    ck = jnp.where(top_k > 0, lo_k, zero)          # top_k <= 0: disabled
    # min-p in logit space: prob >= min_p * max-prob ⟺ x >= top +
    # log(min_p) (log 0 = -inf keeps everything when min_p is off)
    cm = _monotone_key((top + jnp.log(min_p)[:, None]) + 0.0)[:, 0]
    cutoff = jnp.maximum(jnp.maximum(ck, hi_p), cm)
    cutoff = jnp.minimum(cutoff, jnp.max(keys, axis=-1))   # argmax survives
    return jnp.where(keys >= cutoff[:, None], x, -jnp.inf)


def sample_step(logits: jax.Array, seed: jax.Array, q: jax.Array,
                temp: jax.Array, top_k: jax.Array, top_p: jax.Array,
                min_p: jax.Array) -> jax.Array:
    """Per-slot categorical sampling inside the compiled decode step.

    logits: (B, V); seed/q: (B,) i32; temp/top_p/min_p: (B,) f32;
    top_k: (B,) i32.  Returns (B,) int32 sampled tokens.  ``q`` is the
    absolute cache position the sampled token will occupy: slot b's key is
    ``fold_in(fold_in(PRNGKey(0), seed[b]), q[b])``, so the draw depends on
    nothing but (seed, q) — see the fold-in note above.  Sampling is
    Gumbel-argmax over :func:`masked_logits` (exact categorical over the
    renormalised kept set).  ``temp <= 0`` short-circuits to the plain
    argmax bit-exactly — the greedy path is unchanged by this transform.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    x = masked_logits(logits, temp, top_k, top_p, min_p)
    v = x.shape[-1]

    def draw(s, qq):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), s), qq)
        return jax.random.gumbel(key, (v,), jnp.float32)

    g = jax.vmap(draw)(seed, q)
    stoch = jnp.argmax(x + g, axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0, stoch, greedy)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10_000.0, dim / d)
    pe = jnp.zeros((n, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    pe = pe.at[:, 1::2].set(jnp.cos(angle[:, : (d + 1) // 2]))
    return pe
