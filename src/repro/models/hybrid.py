"""Hymba-style hybrid layer: parallel attention + SSM heads [arXiv:2411.13676].

Each layer runs an attention branch and a Mamba2 (SSD) branch on the same
input in parallel, normalises each branch output and averages them, then a
gated MLP.  Per the Hymba recipe, most layers use sliding-window attention
(cfg.attn_window) and ``n_global_layers`` layers (first / middle / last) use
full attention — expressed as a per-layer window array threaded through the
scanned stack (``layer_xs``), so the single compiled layer body serves both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mamba2
from repro.models import transformer as T

RULES = L.RULES


def window_schedule(cfg) -> jax.Array:
    """(L,) int32: per-layer attention window; >= max_seq means global."""
    lcount = cfg.n_layers
    glob = {0, lcount // 2, lcount - 1} if cfg.n_global_layers >= 3 \
        else set(range(cfg.n_global_layers))
    win = [cfg.max_seq + 1 if i in glob else cfg.attn_window
           for i in range(lcount)]
    return jnp.asarray(win, jnp.int32)


def hybrid_layer_init(key, cfg) -> dict:
    ka, km, kmlp = jax.random.split(key, 3)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
        "attn": L.attention_init(ka, cfg, cfg.pdtype),
        "attn_norm": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
        "mamba": mamba2.mamba_params_init(km, cfg),
        "mamba_norm": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
        "ln2": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
        "mlp": L.mlp_init(kmlp, cfg.d_model, cfg.d_ff, cfg.act, cfg.pdtype),
    }


def hybrid_layer_apply(p, cfg, x, extra, *, positions, rules=RULES):
    """extra: per-layer window (traced int32 scalar from window_schedule)."""
    h = L.rmsnorm(p["ln1"], x, cfg.rms_eps)
    a = L.attention(p["attn"], cfg, h, positions=positions, causal=True,
                    window=extra, rules=rules)
    m = mamba2.mamba_apply(p["mamba"], cfg, h, rules=rules)
    mix = 0.5 * (L.rmsnorm(p["attn_norm"], a, cfg.rms_eps)
                 + L.rmsnorm(p["mamba_norm"], m, cfg.rms_eps))
    x = x + mix
    h2 = L.rmsnorm(p["ln2"], x, cfg.rms_eps)
    x = x + L.mlp(p["mlp"], cfg, h2, rules=rules)
    return x, jnp.zeros((), jnp.float32)


def hybrid_layer_decode_rows(p, cfg, x_t, cache, li, pos, extra, *,
                             rules=RULES):
    """Decode step of layer ``li`` against the read-only stacked {kv,
    mamba} cache — the K/V arena read in place by flash-decode, the SSD
    state sliced to the layer; emits the attention branch's K/V rows and
    the SSD branch's new state for the driver's single arena write (the
    rows/arena contract).

    Both branches ride the shared ``decode_and_sample`` driver: sampled
    decode stays deterministic under preemption because the attention KV
    is position-addressed and the SSD state is re-derived by the replayed
    prefill, while the draw at each position depends only on (seed,
    position) — see mamba2.ssm_layer_decode_rows for the recurrent-state
    half of that argument."""
    h = L.rmsnorm(p["ln1"], x_t, cfg.rms_eps)
    a, rows = L.attention_decode_rows(p["attn"], cfg, h, cache["kv"], li,
                                      pos, window=extra, rules=rules)
    m, m_state = mamba2.mamba_decode_step(
        p["mamba"], cfg, h, L.layer_view(cache["mamba"], li), rules=rules)
    mix = 0.5 * (L.rmsnorm(p["attn_norm"], a, cfg.rms_eps)
                 + L.rmsnorm(p["mamba_norm"], m, cfg.rms_eps))
    x_t = x_t + mix
    h2 = L.rmsnorm(p["ln2"], x_t, cfg.rms_eps)
    x_t = x_t + L.mlp(p["mlp"], cfg, h2, rules=rules)
    return x_t, {"kv": {"k": rows[0], "v": rows[1]}, "mamba": m_state}


def hybrid_rows_scatter(cache, emits, pos):
    """One decode step's arena write for the cache pair: K/V rows scatter
    at each slot's ``pos`` column (parked slots drop out of bounds), SSD
    state emissions keep-masked on ``pos`` (see mamba2.ssm_rows_scatter)."""
    return {"kv": T.dense_rows_scatter(cache["kv"], emits["kv"], pos),
            "mamba": mamba2.ssm_rows_scatter(cache["mamba"], emits["mamba"],
                                             pos)}


def hybrid_layer_chunk(p, cfg, x, cache_l, positions, start, nvalid, extra,
                       *, rules=RULES):
    """One prompt chunk through both branches: chunk-append attention
    (per-layer window from the scanned schedule) over the slot's KV
    prefix, and the SSD chunk recurrence threaded through the slot's
    state (reset at start == 0, padding masked via ``nvalid`` — see
    mamba2.ssm_layer_chunk)."""
    state0, tail0 = mamba2.chunk_carry(cache_l["mamba"], start)
    h = L.rmsnorm(p["ln1"], x, cfg.rms_eps)
    a, rows = L.attention_chunk(p["attn"], cfg, h, cache_l["kv"], positions,
                                start, window=extra, rules=rules)
    m, (state, conv_tail) = mamba2.mamba_apply(
        p["mamba"], cfg, h, rules=rules, initial_state=state0,
        conv_tail=tail0, nvalid=nvalid, return_state=True)
    mix = 0.5 * (L.rmsnorm(p["attn_norm"], a, cfg.rms_eps)
                 + L.rmsnorm(p["mamba_norm"], m, cfg.rms_eps))
    x = x + mix
    h2 = L.rmsnorm(p["ln2"], x, cfg.rms_eps)
    x = x + L.mlp(p["mlp"], cfg, h2, rules=rules)
    return x, {"kv": {"k": rows[0], "v": rows[1]},
               "mamba": {"ssm": state,
                         "conv": conv_tail.astype(cfg.adtype)}}


def hybrid_chunk_scatter(cache, emits, slot, start):
    """One chunk's arena write for the cache pair: K/V chunk rows at
    [slot, start:start+C], SSD carry at the slot's fused head rows — both
    drop an out-of-range (parked) slot instead of clamping onto the last
    live slot."""
    return {"kv": T.dense_chunk_scatter(cache["kv"], emits["kv"], slot,
                                        start),
            "mamba": mamba2.ssm_chunk_scatter(cache["mamba"],
                                              emits["mamba"], slot, start)}


def init_hybrid_cache(cfg, batch: int, max_seq: int) -> dict:
    return {
        "kv": L.init_kv_cache(cfg, batch, max_seq),
        "mamba": mamba2.init_ssm_cache(cfg, batch, max_seq),
    }


def hybrid_prefill_layer(p, cfg, x, cache_l, positions, extra, *,
                         rules=RULES):
    """Prefill both branches: attention KV fill + SSD state carry-out."""
    from repro.models import transformer as T
    h = L.rmsnorm(p["ln1"], x, cfg.rms_eps)
    a, kv_cache = T.attention_prefill(p["attn"], cfg, h, cache_l["kv"],
                                      positions, window=extra, rules=rules)
    m, (state, conv_tail) = mamba2.mamba_apply(p["mamba"], cfg, h,
                                               rules=rules,
                                               return_state=True)
    mix = 0.5 * (L.rmsnorm(p["attn_norm"], a, cfg.rms_eps)
                 + L.rmsnorm(p["mamba_norm"], m, cfg.rms_eps))
    x = x + mix
    h2 = L.rmsnorm(p["ln2"], x, cfg.rms_eps)
    x = x + L.mlp(p["mlp"], cfg, h2, rules=rules)
    new_cache = {"kv": kv_cache,
                 "mamba": {"ssm": state,
                           "conv": conv_tail.astype(cfg.adtype)}}
    return x, new_cache
