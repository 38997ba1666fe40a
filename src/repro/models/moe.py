"""Mixture-of-Experts layer: top-k routing, two dispatch paths.

Serving (:func:`moe_mlp_serve`, every serving hook) is **dropless** and
computes only the experts this chip holds (``MoEConfig.experts_held``
from ``first_expert``; all of them by default):

  1. router logits over all ``n_experts`` in f32, softmax, top-k, weights
     renormalised over the k when ``norm_topk_prob``,
  2. the (token, choice) rows that land on a held expert are sorted by
     expert (a one-hot cumsum gives each row its place; no sort, no
     capacity) — rows routed elsewhere, pad tokens and parked slots land
     nowhere,
  3. ``expert_gmm`` (kernels/expert_gmm.py) multiplies each expert's rows
     by its gate and up weights, then silu(gate) * up by its down weights,
     fetching no weight of an expert that received no row,
  4. each row returns to its token scaled by its routing weight.

Nothing is dropped, so a token's output never depends on its batch-mates.
The layer also counts, per call, the rows routed here and the held
experts that received at least one (the engine's ``moe_*`` stats).

Training (:func:`moe_mlp_apply`) keeps capacity dispatch, implemented
sort-free via cumulative-count positioning:

  1. router logits -> top-k experts + gates per token,
  2. position-in-expert via a masked cumsum over the (tokens·k, E) one-hot
     (the predication mass of the paper: capacity dropping == RVV
     tail-undisturbed masking — dropped tokens keep their residual value),
  3. gather tokens into a dense (E, C, d) dispatch buffer (EP: E over the
     lane axis, C over data),
  4. per-expert gated-MLP matmuls — dense MXU work,
  5. weighted scatter-add back (combine).

The dispatch/combine gathers are the MoE "monolithic crossbar" (paper
Eq. 2): under GSPMD they lower to all-to-all/all-gather traffic measured by
the collective roofline term; the hierarchical alternative is a §Perf
iteration.  A Switch-style load-balance aux loss + router z-loss are
returned for the trainer.  Training holds every expert.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.core import lanes
from repro.kernels import ops
from repro.models import layers as L

RULES = L.RULES

# the serving layer's step counters, in the order of its ``counts``
COUNTERS = ("moe_rows_here", "moe_experts_touched")


def moe_mlp_init(key, cfg) -> dict:
    me = cfg.moe
    d, dff = cfg.d_model, me.d_ff_expert
    kr, ke, ks, kg = jax.random.split(key, 4)
    s_in, s_out = d ** -0.5, dff ** -0.5

    def expert_block(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "w_gate": (jax.random.normal(k1, (d, dff)) * s_in).astype(cfg.pdtype),
            "w_up": (jax.random.normal(k2, (d, dff)) * s_in).astype(cfg.pdtype),
            "w_down": (jax.random.normal(k3, (dff, d)) * s_out).astype(cfg.pdtype),
        }

    p = {
        "router": (jax.random.normal(kr, (d, me.n_experts)) * s_in)
        .astype(jnp.float32),
        "experts": jax.vmap(expert_block)(jax.random.split(ke, me.held)),
    }
    if me.n_shared_experts:
        p["shared"] = L.mlp_init(ks, d, me.d_ff_shared, "silu_gated",
                                 cfg.pdtype)
        p["shared_gate"] = (jax.random.normal(kg, (d, 1)) * s_in) \
            .astype(cfg.pdtype)
    return p


# MoE dispatch lowering (§Perf cell-2 hillclimb):
#   "global" — routing/cumsum/gather on the full token axis; GSPMD lowers
#              the cross-shard gathers as f32 all-reduces of the whole
#              (E·C, d) dispatch buffer per layer (baseline, REFUTED as a
#              production config by the dry-run wire term).
#   "local"  — shard_map manual over the DP axes: each data shard routes
#              its local tokens with local capacity; only the expert
#              einsums cross the lane axis (proper EP all-to-all).
MOE_DISPATCH: str = "global"


def set_moe_dispatch(mode: str) -> None:
    global MOE_DISPATCH
    if mode not in ("global", "local"):
        raise ValueError(mode)
    MOE_DISPATCH = mode


def moe_mlp_apply(p, cfg, x, *, rules=RULES):
    """x: (B, S, d) -> (y, aux_loss).  Dispatch per MOE_DISPATCH."""
    if MOE_DISPATCH == "local":
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.empty:
            dp = tuple(a for a in (lanes.POD_AXIS, lanes.DATA_AXIS)
                       if a in mesh.axis_names
                       and mesh.axis_types[mesh.axis_names.index(a)]
                       != AxisType.Manual
                       and mesh.shape[a] > 1)
            dp_size = 1
            for a in dp:
                dp_size *= mesh.shape[a]
            if dp and x.shape[0] % dp_size == 0:
                from jax.sharding import PartitionSpec as P

                # Param dtype across the shard_map boundary: the transpose
                # of replicated-in params is a psum of the weight
                # cotangents over the manual axes, and the CPU XLA backend
                # miscompiles 16-bit psum there ("invalid binary opcode
                # copy") — so params cross in f32 on CPU (bf16 on TPU,
                # where the bug does not exist and the wire halves).
                wdt = jnp.bfloat16 if jax.default_backend() == "tpu" \
                    else jnp.float32
                p_in = jax.tree.map(
                    lambda a: a.astype(wdt)
                    if a.dtype == jnp.bfloat16 else a, p)

                def body(p_, x_loc):
                    y, aux = _moe_mlp_global(p_, cfg, x_loc, rules=rules)
                    return y.astype(x.dtype), jax.lax.pmean(aux, dp)

                return jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(P(), P(dp if len(dp) > 1 else dp[0])),
                    out_specs=(P(dp if len(dp) > 1 else dp[0]), P()),
                    axis_names=set(dp), check_vma=False)(p_in, x)
    return _moe_mlp_global(p, cfg, x, rules=rules)


def _moe_mlp_global(p, cfg, x, *, rules=RULES):
    """Routing + dispatch + expert MLPs + combine over x's token axis."""
    me = cfg.moe
    if me.held != me.n_experts:
        raise ValueError("capacity dispatch needs every expert held; "
                         "serve a partial share with moe_mlp_serve")
    b, s, d = x.shape
    t = b * s
    e, k = me.n_experts, me.top_k
    xf = x.reshape(t, d)

    # -- routing ------------------------------------------------------------
    logits = jnp.dot(xf.astype(jnp.float32), p["router"])        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_idx = jax.lax.top_k(probs, k)                  # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # aux losses (Switch LB + z-loss)
    density = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e), axis=0)
    mean_prob = probs.mean(axis=0)
    aux = me.router_aux_weight * e * jnp.sum(density * mean_prob)
    zloss = me.router_z_weight * jnp.mean(
        jax.nn.logsumexp(logits, -1) ** 2)
    aux = aux + zloss

    # -- dispatch positions (predicated, sort-free) ---------------------------
    flat_e = expert_idx.reshape(-1)                              # (T*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)          # (T*k, E)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)             # exclusive
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    cap = max(int(k * t * me.capacity_factor / e), 1)
    keep = pos < cap                                             # predication
    slot = flat_e * cap + pos                                    # (T*k,)
    slot = jnp.where(keep, slot, e * cap)                        # overflow row

    # -- gather into (E, C, d) ------------------------------------------------
    token_of = jnp.arange(t).repeat(k)                           # (T*k,)
    buf_tok = jnp.full((e * cap + 1,), t, jnp.int32)
    buf_tok = buf_tok.at[slot].set(jnp.where(keep, token_of, t))
    buf_tok = buf_tok[:-1]                                       # (E*C,)
    xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], 0)
    xe = xf_pad[buf_tok].reshape(e, cap, d)                      # (E, C, d)
    xe = lanes.constrain(xe, rules, "expert", "capacity", None)

    # -- expert MLPs (dense MXU work) -----------------------------------------
    we = p["experts"]
    adt = cfg.adtype
    hg = jnp.einsum("ecd,edf->ecf", xe, we["w_gate"],
                    preferred_element_type=jnp.float32)
    hu = jnp.einsum("ecd,edf->ecf", xe, we["w_up"],
                    preferred_element_type=jnp.float32)
    h = (jax.nn.silu(hg) * hu).astype(adt)
    # EP: the expert dim owns the lane axis; the per-expert hidden dim must
    # NOT also map to lanes (one mesh axis can shard at most one dim)
    h = lanes.constrain(h, rules, "expert", "capacity", None)
    ye = jnp.einsum("ecf,efd->ecd", h, we["w_down"],
                    preferred_element_type=jnp.float32).astype(adt)
    ye = lanes.constrain(ye, rules, "expert", "capacity", None)

    # -- combine (weighted scatter-add; dropped tokens contribute nothing) ----
    yf = ye.reshape(e * cap, d)
    flat_gate = gates.reshape(-1) * keep                         # (T*k,)
    slot_safe = jnp.where(keep, flat_e * cap + pos, 0)
    contrib = yf[slot_safe] * flat_gate[:, None].astype(adt)
    y = jnp.zeros((t, d), jnp.float32).at[token_of].add(
        contrib.astype(jnp.float32))

    # -- shared experts (always-on path) ---------------------------------------
    if me.n_shared_experts:
        sh = L.mlp(p["shared"], cfg, xf, act="silu_gated", rules=rules)
        sgate = jax.nn.sigmoid(
            jnp.dot(xf.astype(jnp.float32), p["shared_gate"]
                    .astype(jnp.float32)))
        y = y + sh.astype(jnp.float32) * sgate

    y = y.astype(adt).reshape(b, s, d)
    return lanes.constrain(y, rules, "batch", None, "embed"), aux


def route(router, me, h):
    """h (T, d) -> (experts (T, k) int32, weights (T, k) f32): softmax of
    the f32 router logits over all ``n_experts``, the top k, renormalised
    over the k when ``norm_topk_prob``."""
    logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                     me.top_k)
    if me.norm_topk_prob:
        weights = weights / weights.sum(-1, keepdims=True)
    return experts, weights


def moe_mlp_serve(p, cfg, x, valid):
    """The dropless expert layer over the experts held here.

    x: (..., d); valid: x's leading shape, bool — False rows (chunk
    padding, parked slots) route nowhere.  ``p["experts"]`` is this
    layer's, or, under ``p["layer"]``, every layer's stacked (read in
    place by ``expert_gmm``).  Returns (y like x, counts (2,)
    int32: rows routed to held experts, held experts with a row)."""
    me = cfg.moe
    lead, d = x.shape[:-1], x.shape[-1]
    xf = x.reshape(-1, d)
    t, k, held = xf.shape[0], me.top_k, me.held
    experts, weights = route(p["router"], me, xf)
    local = experts - me.first_expert
    here = (local >= 0) & (local < held) & valid.reshape(t, 1)   # (T, k)
    # each row's place in the expert-sorted buffer: held experts' rows
    # first, in expert order, then every row routed elsewhere
    group = jnp.where(here, local, held).reshape(-1)              # (T*k,)
    onehot = jax.nn.one_hot(group, held + 1, dtype=jnp.int32)
    sizes = onehot.sum(0)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0) - onehot,
                               group[:, None], axis=1)[:, 0]
    dest = jnp.cumsum(sizes)[group] - sizes[group] + rank
    token = jnp.zeros((t * k,), jnp.int32).at[dest].set(
        jnp.arange(t * k, dtype=jnp.int32) // k)
    rows = xf[token]                                              # sorted
    sizes = sizes[:held]
    we, li = p["experts"], p.get("layer")
    gate = ops.expert_gmm(rows, we["w_gate"], sizes, layer=li)
    up = ops.expert_gmm(rows, we["w_up"], sizes, layer=li)
    h = (jax.nn.silu(gate.astype(jnp.float32))
         * up.astype(jnp.float32)).astype(cfg.adtype)
    out = ops.expert_gmm(h, we["w_down"], sizes, layer=li)
    # rows past the held experts' hold no defined value: select, never
    # multiply, them away
    back = out[dest].reshape(t, k, d).astype(jnp.float32)
    y = jnp.where(here[..., None], back * weights[..., None], 0.0).sum(1)
    if me.n_shared_experts:
        sh = L.mlp(p["shared"], cfg, xf, act="silu_gated")
        sgate = jax.nn.sigmoid(
            jnp.dot(xf.astype(jnp.float32), p["shared_gate"]
                    .astype(jnp.float32)))
        y = y + sh.astype(jnp.float32) * sgate
    counts = jnp.stack([sizes.sum(), (sizes > 0).sum()]).astype(jnp.int32)
    return y.astype(cfg.adtype).reshape(*lead, d), counts


def moe_layer_init(key, cfg) -> dict:
    ka, km = jax.random.split(key)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
        "attn": L.attention_init(ka, cfg, cfg.pdtype),
        "ln2": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
        "moe": moe_mlp_init(km, cfg),
    }


def moe_layer_apply(p, cfg, x, extra=None, *, positions, rules=RULES):
    h = L.rmsnorm(p["ln1"], x, cfg.rms_eps)
    x = x + L.attention(p["attn"], cfg, h, positions=positions,
                        causal=True, rules=rules)
    h = L.rmsnorm(p["ln2"], x, cfg.rms_eps)
    y, aux = moe_mlp_apply(p["moe"], cfg, h, rules=rules)
    return x + y, aux


def moe_prefill_layer(p, cfg, x, cache_l, positions, extra=None, *,
                      rules=RULES):
    """Prefill: attention + KV fill (shared helper) + the dropless expert
    layer over every prompt token."""
    from repro.models import transformer as T
    h = L.rmsnorm(p["ln1"], x, cfg.rms_eps)
    a, cache_l = T.attention_prefill(p["attn"], cfg, h, cache_l, positions,
                                     rules=rules)
    x = x + a
    h = L.rmsnorm(p["ln2"], x, cfg.rms_eps)
    y, _ = moe_mlp_serve(p["moe"], cfg, h, jnp.ones(h.shape[:-1], bool))
    return x + y, cache_l


def moe_layer_chunk(p, cfg, x, kv_l, positions, start, nvalid, extra=None,
                    *, rules=RULES):
    """One prompt chunk through an MoE layer: chunk-append attention over
    the slot's KV prefix + the dropless expert layer on the chunk's first
    ``nvalid`` tokens (padding routes nowhere); emits the chunk's K/V rows
    for the driver's single arena scatter and the layer's step counters
    (``T.COUNTS``)."""
    from repro.models import transformer as T
    h = L.rmsnorm(p["ln1"], x, cfg.rms_eps)
    a, rows = L.attention_chunk(p["attn"], cfg, h, kv_l, positions, start,
                                rules=rules)
    x = x + a
    h = L.rmsnorm(p["ln2"], x, cfg.rms_eps)
    valid = jnp.arange(x.shape[1])[None, :] < nvalid
    y, counts = moe_mlp_serve(p["moe"], cfg, h,
                              jnp.broadcast_to(valid, h.shape[:-1]))
    return x + y, dict(T.kv_emit_dict(rows), **{T.COUNTS: counts})


def moe_layer_decode_rows(p, cfg, x_t, kv, li, pos, extra=None, *,
                          rules=RULES):
    """Decode step of layer ``li`` against the read-only stacked KV arena
    (read in place by flash-decode); emits the token's K/V rows for the
    driver's single arena scatter and the layer's step counters.  A parked
    slot (``pos == PARKED_POS``) routes nowhere, so it fetches no expert's
    weights."""
    from repro.models import transformer as T
    h = L.rmsnorm(p["ln1"], x_t, cfg.rms_eps)
    a, rows = L.attention_decode_rows(p["attn"], cfg, h, kv, li, pos,
                                      rules=rules)
    x_t = x_t + a
    h = L.rmsnorm(p["ln2"], x_t, cfg.rms_eps)
    y, counts = moe_mlp_serve(p["moe"], cfg, h, pos < L.PARKED_POS)
    return x_t + y, dict(T.kv_emit_dict(rows), **{T.COUNTS: counts})
