"""Mamba2 (SSD) layer — attention-free state-space stack [arXiv:2405.21060].

The chunked SSD computation maps 1:1 onto the paper's execution model (see
kernels/ssd.py): strip-mined chunks, lane-local dense work, a small state
carried across strips.  Serving keeps an O(N·P) recurrent state per head —
no KV cache — which is why this arch runs the long_500k cell.

Layer: in-proj -> depthwise causal conv(4) on (x, B, C) -> SSD -> gated
RMSNorm -> out-proj, as in the reference Mamba2 block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import lanes
from repro.kernels import ops
from repro.models import layers as L

RULES = L.RULES


def mamba_params_init(key, cfg) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    gn = s.n_groups * s.d_state
    kz, kx, kb, kc, kdt, ko, kconv = jax.random.split(key, 7)
    sc = d ** -0.5
    dt = jnp.exp(jax.random.uniform(kdt, (nh,), minval=jnp.log(1e-3),
                                    maxval=jnp.log(1e-1)))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))   # inverse softplus
    return {
        "w_z": (jax.random.normal(kz, (d, di)) * sc).astype(cfg.pdtype),
        "w_x": (jax.random.normal(kx, (d, di)) * sc).astype(cfg.pdtype),
        "w_B": (jax.random.normal(kb, (d, gn)) * sc).astype(cfg.pdtype),
        "w_C": (jax.random.normal(kc, (d, gn)) * sc).astype(cfg.pdtype),
        "w_dt": (jax.random.normal(kdt, (d, nh)) * sc).astype(cfg.pdtype),
        "conv": (jax.random.normal(kconv, (s.conv_width, di + 2 * gn))
                 * 0.1).astype(cfg.pdtype),
        "A_log": jnp.zeros((nh,), jnp.float32),          # A = -exp(A_log)
        "dt_bias": dt_bias.astype(jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "norm": L.rmsnorm_init(di, cfg.pdtype),
        "w_out": (jax.random.normal(ko, (di, d)) * di ** -0.5)
        .astype(cfg.pdtype),
    }


def _causal_depthwise_conv(x, w, tail=None):
    """x: (B, S, C), w: (W, C) — causal depthwise conv along S.

    ``tail``: optional (B, W-1, C) *raw* channel inputs preceding ``x``
    (the stored conv state of a chunked/streaming caller); absent ⟹ zero
    history, the sequence-start case."""
    wlen = w.shape[0]
    if tail is None:
        xp = jnp.pad(x, ((0, 0), (wlen - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(wlen):
        out = out + xp[:, i:i + x.shape[1]].astype(jnp.float32) \
            * w[i].astype(jnp.float32)
    return out.astype(x.dtype)


def mamba_apply(p, cfg, x, *, rules=RULES, initial_state=None,
                conv_tail=None, nvalid=None, return_state: bool = False):
    """x: (B, S, d) -> y (B, S, d) [+ (ssm_state, conv_tail)].

    Streaming/chunked extension (the SSD chunk recurrence of serving's
    stripmined prefill): ``initial_state`` (B·nh, N, P) and ``conv_tail``
    (B, W-1, di+2gn raw pre-conv inputs) carry the recurrence across
    chunk boundaries — both None at sequence start.  ``nvalid`` (traced
    int32, None ⟹ S) marks the first ``nvalid`` positions as real; pad
    positions beyond it are masked out of the recurrence (x̄ → 0, decay
    → 1), so the returned state equals the state after the real tokens
    alone and the final chunk's padding never pollutes the carry.  The
    returned conv tail is the last W-1 raw inputs *ending at* position
    nvalid — drawn from the [tail ; chunk] history, so it is correct even
    when a chunk holds fewer than W-1 real tokens.
    """
    s = cfg.ssm
    b, seq, d = x.shape
    di = s.d_inner(d)
    nh = s.n_heads(d)
    hd = s.headdim
    gn = s.n_groups * s.d_state
    n = s.d_state
    adt = cfg.adtype

    z = L._dot(x, p["w_z"], adt)                          # (B,S,di)
    xin = L._dot(x, p["w_x"], adt)
    Bv = L._dot(x, p["w_B"], adt)
    Cv = L._dot(x, p["w_C"], adt)
    dt = jnp.dot(x.astype(jnp.float32), p["w_dt"].astype(jnp.float32))

    xbc_raw = jnp.concatenate([xin, Bv, Cv], axis=-1)
    xbc = jax.nn.silu(_causal_depthwise_conv(xbc_raw, p["conv"], conv_tail)
                      .astype(jnp.float32)).astype(adt)
    xin, Bv, Cv = jnp.split(xbc, [di, di + gn], axis=-1)
    xin = lanes.constrain(xin, rules, "batch", None, "ffn")

    dt = jax.nn.softplus(dt + p["dt_bias"])               # (B,S,nh) f32
    A = -jnp.exp(p["A_log"])                              # (nh,)
    log_a = dt * A                                        # (B,S,nh)

    # head split; fold dt into x (x̄ = dt * x)
    xh = xin.reshape(b, seq, nh, hd).astype(jnp.float32) * dt[..., None]
    if nvalid is not None:
        # pad predication (RVV tail-undisturbed on the *state*): x̄ = 0 and
        # log-decay = 0 at pad positions ⟹ state_{i} = state_{i-1} there,
        # so the carry-out is exactly the state after the real tokens
        live = (jnp.arange(seq) < nvalid).astype(jnp.float32)
        xh = xh * live[None, :, None, None]
        log_a = log_a * live[None, :, None]
    # group -> head broadcast (n_groups=1): B/C shared across heads
    Bh = jnp.broadcast_to(Bv.reshape(b, seq, s.n_groups, n)[:, :, :1],
                          (b, seq, nh, n)) if s.n_groups == 1 else \
        Bv.reshape(b, seq, s.n_groups, n).repeat(nh // s.n_groups, 2)
    Ch = jnp.broadcast_to(Cv.reshape(b, seq, s.n_groups, n)[:, :, :1],
                          (b, seq, nh, n)) if s.n_groups == 1 else \
        Cv.reshape(b, seq, s.n_groups, n).repeat(nh // s.n_groups, 2)

    def bh(t):   # (B,S,H,*) -> (B*H, S, *)
        return t.transpose(0, 2, 1, 3).reshape(b * nh, seq, t.shape[-1])

    y, state = ops.ssd(
        bh(xh).astype(adt),
        log_a.transpose(0, 2, 1).reshape(b * nh, seq),
        bh(Bh).astype(adt), bh(Ch).astype(adt),
        chunk=s.chunk, initial_state=initial_state)
    y = y.reshape(b, nh, seq, hd).transpose(0, 2, 1, 3).astype(jnp.float32)
    y = y + p["D"][None, None, :, None] * xh              # skip connection
    y = y.reshape(b, seq, di).astype(adt)

    y = L.rmsnorm(p["norm"], y * jax.nn.silu(z.astype(jnp.float32))
                  .astype(adt), cfg.rms_eps)
    out = L._dot(y, p["w_out"], adt)
    out = lanes.constrain(out, rules, "batch", None, "embed")
    if return_state:
        # conv state = the W-1 *raw* (pre-conv) channel inputs ending at
        # the last real position, drawn from the [tail ; chunk] history so
        # short final chunks (real < W-1) pull the missing rows from the
        # previous chunk's stored tail instead of under-filling
        wtail = s.conv_width - 1
        hist = (jnp.pad(xbc_raw, ((0, 0), (wtail, 0), (0, 0)))
                if conv_tail is None else
                jnp.concatenate([conv_tail.astype(xbc_raw.dtype), xbc_raw],
                                axis=1))
        end = seq if nvalid is None else nvalid
        new_tail = jax.lax.dynamic_slice(
            hist, (0, end, 0), (b, wtail, hist.shape[-1]))
        return out, (state, new_tail)
    return out


def mamba_decode_step(p, cfg, x_t, cache, *, rules=RULES):
    """One-token recurrence. x_t: (B, d); cache: {"ssm": (B*nh, N, P),
    "conv": (B, W-1, di+2gn)}."""
    s = cfg.ssm
    b, d = x_t.shape
    di = s.d_inner(d)
    nh = s.n_heads(d)
    hd = s.headdim
    gn = s.n_groups * s.d_state
    n = s.d_state
    adt = cfg.adtype

    z = L._dot(x_t, p["w_z"], adt)
    xin = L._dot(x_t, p["w_x"], adt)
    Bv = L._dot(x_t, p["w_B"], adt)
    Cv = L._dot(x_t, p["w_C"], adt)
    dt = jnp.dot(x_t.astype(jnp.float32), p["w_dt"].astype(jnp.float32))

    xbc_t = jnp.concatenate([xin, Bv, Cv], axis=-1)       # (B, di+2gn)
    hist = jnp.concatenate([cache["conv"], xbc_t[:, None]], axis=1)
    w = p["conv"]
    conv_out = (hist.astype(jnp.float32)
                * w[None].astype(jnp.float32)).sum(axis=1)
    xbc = jax.nn.silu(conv_out).astype(adt)
    new_conv = hist[:, 1:]
    xin, Bv, Cv = jnp.split(xbc, [di, di + gn], axis=-1)

    dt = jax.nn.softplus(dt + p["dt_bias"])               # (B, nh)
    A = -jnp.exp(p["A_log"])
    log_a = (dt * A).reshape(b * nh)
    xh = (xin.reshape(b, nh, hd).astype(jnp.float32)
          * dt[..., None]).reshape(b * nh, hd)
    Bh = jnp.broadcast_to(Bv.reshape(b, s.n_groups, n)[:, :1],
                          (b, nh, n)).reshape(b * nh, n)
    Ch = jnp.broadcast_to(Cv.reshape(b, s.n_groups, n)[:, :1],
                          (b, nh, n)).reshape(b * nh, n)

    y, new_state = ops.ssd_decode_step(xh.astype(adt), log_a,
                                       Bh.astype(adt), Ch.astype(adt),
                                       cache["ssm"])
    y = y.reshape(b, nh, hd).astype(jnp.float32) \
        + p["D"][None, :, None] * xh.reshape(b, nh, hd)
    y = y.reshape(b, di).astype(adt)
    y = L.rmsnorm(p["norm"], y * jax.nn.silu(z.astype(jnp.float32))
                  .astype(adt), cfg.rms_eps)
    out = L._dot(y, p["w_out"], adt)
    return out, {"ssm": new_state, "conv": new_conv}


# ---------------------------------------------------------------------------
# layer plumbing for the LM stack
# ---------------------------------------------------------------------------

def ssm_layer_init(key, cfg) -> dict:
    return {
        "ln": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
        "mamba": mamba_params_init(key, cfg),
    }


def ssm_layer_apply(p, cfg, x, extra=None, *, positions=None, rules=RULES):
    h = L.rmsnorm(p["ln"], x, cfg.rms_eps)
    return x + mamba_apply(p["mamba"], cfg, h, rules=rules), \
        jnp.zeros((), jnp.float32)


def ssm_layer_decode_rows(p, cfg, x_t, cache, li, pos, extra=None, *,
                          rules=RULES):
    """Decode step of layer ``li`` against its slice of the read-only
    stacked (ssm, conv) state; emits the layer's *new* state as the scan's
    ys instead of threading the arena (the rows/arena contract — for a
    recurrent cache the "rows" are the whole per-slot state, which the
    recurrence rewrites every step anyway).

    Unlike KV caches the SSD state is not position-addressed, so a
    preempted slot cannot rewind it — recompute replays prefill from the
    prompt and re-derives the state (chunked prefill resets the carry at
    start == 0).  Sampled decode survives that replay because
    ``decode_and_sample``'s PRNG keys fold only (seed, absolute
    position): the regenerated state sees the identical token/draw
    sequence, never a stored RNG cursor."""
    h = L.rmsnorm(p["ln"], x_t, cfg.rms_eps)
    y, new_state = mamba_decode_step(p["mamba"], cfg, h,
                                     L.layer_view(cache, li), rules=rules)
    return x_t + y, new_state


def ssm_rows_scatter(cache, emits, pos):
    """Write one decode step's state emissions into the resident arena.

    ``emits`` is the scan's ys — the full new stacked state (every element
    of an SSD state changes every step: that is the recurrence, not a
    copy) — masked per slot so a parked slot (``pos == layers.PARKED_POS``,
    mid-chunked-prefill) keeps the state its prompt chunks are threading:
    SSD state is not position-addressed, so the KV path's OOB-scatter-drop
    protection must be expressed as an explicit keep-mask here.  The
    elementwise select fuses into the (donated) arena update in place."""
    b = pos.shape[0]
    live = pos < L.PARKED_POS                              # (B,)

    def mix(new, old):
        f = new.shape[1] // b                              # fused B·f leaves
        m = jnp.repeat(live, f).reshape((1, b * f) + (1,) * (new.ndim - 2))
        return jnp.where(m, new.astype(old.dtype), old)

    return jax.tree.map(mix, emits, cache)


def chunk_carry(cache_l, start):
    """The SSD carry-in for a prompt chunk at position ``start``:
    ``(state0, conv_tail0)`` — the slot's threaded state on a continuation
    chunk, zeros on the first chunk.  The reset is load-bearing: a slot's
    previous occupant leaves a stale recurrent state behind (KV rows are
    merely overwritten/never attended, but a recurrence must be re-zeroed
    explicitly or the stale carry leaks into the new request).  Shared by
    the ssm and hybrid chunk layers so the guard exists exactly once."""
    continuing = start > 0          # False on the first chunk: reset carry
    state0 = jnp.where(continuing, cache_l["ssm"].astype(jnp.float32), 0.0)
    tail0 = jnp.where(continuing, cache_l["conv"], 0) \
        .astype(cache_l["conv"].dtype)
    return state0, tail0


def ssm_layer_chunk(p, cfg, x, cache_l, positions, start, nvalid,
                    extra=None, *, rules=RULES):
    """One prompt chunk through an SSM layer: the SSD chunk recurrence
    with the carry threaded through the slot's arena state.

    ``cache_l`` is the slot's per-layer state view {"ssm": (nh, N, P),
    "conv": (1, W-1, di+2gn)}.  The first chunk (start == 0) resets the
    carry (see :func:`chunk_carry`).  ``nvalid`` masks the final chunk's
    padding out of the recurrence, so the emitted state is bit-equal to
    the state after the real tokens alone and a preemption replay (chunk
    cursor rewound to 0) re-derives it exactly."""
    state0, tail0 = chunk_carry(cache_l, start)
    h = L.rmsnorm(p["ln"], x, cfg.rms_eps)
    y, (state, conv_tail) = mamba_apply(p["mamba"], cfg, h, rules=rules,
                                        initial_state=state0,
                                        conv_tail=tail0, nvalid=nvalid,
                                        return_state=True)
    return x + y, {"ssm": state, "conv": conv_tail.astype(cfg.adtype)}


def ssm_chunk_scatter(cache, emits, slot, start):
    """Write one chunk's state emissions into slot ``slot`` of the arena:
    the SSD carry {"ssm": (L, nh, N, P)} lands at the slot's fused head
    rows, the conv tail at its batch row — one scatter per leaf, in place
    under donation, O(slot state) bytes per chunk independent of the slot
    count and the chunk's position.  An out-of-range (parked/sentinel)
    ``slot`` scatters out of bounds and is dropped."""
    nl, nh = emits["ssm"].shape[:2]
    li = jnp.arange(nl)[:, None]       # every row indexed by its layer too,
    hidx = slot * nh + jnp.arange(nh)  # as dense_chunk_scatter does
    return {"ssm": cache["ssm"].at[li, hidx[None, :]].set(
                emits["ssm"].astype(cache["ssm"].dtype)),
            "conv": cache["conv"].at[li[:, 0], slot].set(
                emits["conv"][:, 0].astype(cache["conv"].dtype))}


def init_ssm_cache(cfg, batch: int, max_seq: int) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    gn = s.n_groups * s.d_state
    return {
        "ssm": jnp.zeros((batch * nh, s.d_state, s.headdim), jnp.float32),
        "conv": jnp.zeros((batch, s.conv_width - 1, di + 2 * gn), cfg.adtype),
    }


def ssm_prefill_layer(p, cfg, x, cache_l, positions, extra=None, *,
                      rules=RULES):
    h = L.rmsnorm(p["ln"], x, cfg.rms_eps)
    y, (state, conv_tail) = mamba_apply(p["mamba"], cfg, h, rules=rules,
                                        return_state=True)
    return x + y, {"ssm": state, "conv": conv_tail.astype(cfg.adtype)}
