"""Decoder-only LM: generic scanned layer stack + dense layer + drivers.

The layer stack is a single ``lax.scan`` over stacked per-layer parameters
(one compiled layer body regardless of depth — the strip-mining principle
applied to the *layer* axis), with a configurable remat policy.  Families
(dense/moe/ssm/hybrid) plug in their own ``layer_init`` / ``layer_apply``
plus the four serving hooks (``layer_chunk`` / ``chunk_scatter`` /
``layer_decode_rows`` / ``rows_scatter`` — see the LM class docstring);
the drivers (``loss_fn``, ``prefill``, ``prefill_chunk``, ``decode_step``)
are shared by every LM-family architecture.
"""
from __future__ import annotations

import functools
import inspect
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import kv_format as kv_format_mod
from repro.core import lanes
from repro.models import layers as L

RULES = L.RULES

REMAT_POLICIES = {
    "none": None,
    "full": "nothing",
    "dots": "dots_with_no_batch_dims_saveable",
    "save_tp": "save_only_these_names(tp_boundary)",
}


def _maybe_remat(fn, remat: str):
    if remat == "none":
        return fn
    if remat == "full":
        return jax.checkpoint(fn)
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if remat == "save_tp":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "tp_boundary"))
    raise ValueError(f"unknown remat policy {remat!r}")


# ---------------------------------------------------------------------------
# dense layer
# ---------------------------------------------------------------------------

def dense_layer_init(key, cfg) -> dict:
    ka, km, k1, k2 = jax.random.split(key, 4)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
        "attn": L.attention_init(ka, cfg, cfg.pdtype),
        "ln2": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
        "mlp": L.mlp_init(km, cfg.d_model, cfg.d_ff, cfg.act, cfg.pdtype),
    }


def dense_layer_apply(p, cfg, x, *, positions, window=None, rules=RULES):
    h = L.rmsnorm(p["ln1"], x, cfg.rms_eps)
    x = x + L.attention(p["attn"], cfg, h, positions=positions,
                        causal=True, window=window, rules=rules)
    h = L.rmsnorm(p["ln2"], x, cfg.rms_eps)
    x = x + L.mlp(p["mlp"], cfg, h, rules=rules)
    return x, jnp.zeros((), jnp.float32)


def dense_layer_chunk(p, cfg, x, slot_kv, positions, start, *, window=None,
                      rules=RULES):
    """One prompt chunk through a dense layer: chunk-append attention over
    the slot's cache prefix + MLP.  The stripmined counterpart of
    :func:`_prefill_layer` (same math restricted to the chunk's rows).
    ``slot_kv`` is a read-only view of the slot's arena rows; the layer
    returns the chunk's K/V rows for the driver's single arena splice."""
    h = L.rmsnorm(p["ln1"], x, cfg.rms_eps)
    a, rows = L.attention_chunk(p["attn"], cfg, h, slot_kv, positions, start,
                                window=window, rules=rules)
    x = x + a
    h = L.rmsnorm(p["ln2"], x, cfg.rms_eps)
    x = x + L.mlp(p["mlp"], cfg, h, rules=rules)
    return x, rows


def dense_layer_decode_rows(p, cfg, x_t, kv, li, pos, *, window=None,
                            rules=RULES):
    """One decode step through dense layer ``li`` against the read-only
    stacked arena; returns the new K/V rows instead of a rewritten cache
    (see :func:`repro.models.layers.attention_decode_rows`)."""
    h = L.rmsnorm(p["ln1"], x_t, cfg.rms_eps)
    a, rows = L.attention_decode_rows(p["attn"], cfg, h, kv, li, pos,
                                      window=window, rules=rules)
    x_t = x_t + a
    h = L.rmsnorm(p["ln2"], x_t, cfg.rms_eps)
    x_t = x_t + L.mlp(p["mlp"], cfg, h, rules=rules)
    return x_t, rows


# emission key of a layer hook's step counters: an int32 vector the driver
# sums over layers and returns beside the step's outputs (``with_counts``);
# the family's ``LM.counters`` names its entries
COUNTS = "counts"


def _split_counts(emits):
    """(emits without the counters, counters summed over layers or None)."""
    if not (isinstance(emits, dict) and COUNTS in emits):
        return emits, None
    emits = dict(emits)
    return emits, emits.pop(COUNTS).sum(0)


def _split_resident(layers):
    """(stacked layer params the scan slices, the expert weights or None).

    The expert layer reads its weights where they lie, at the layer index
    the scan carries: ``expert_gmm`` is a Pallas call, which cannot fuse
    the scan's per-layer slice, so a sliced operand would first be copied
    whole — every held expert's weights, each layer of each step."""
    moe = layers.get("moe") if isinstance(layers, dict) else None
    if not (isinstance(moe, dict) and "experts" in moe):
        return layers, None
    rest = {k: v for k, v in moe.items() if k != "experts"}
    return dict(layers, moe=rest), moe["experts"]


def _with_resident(lp, experts, li):
    """Layer ``li``'s params with the whole expert stack and its index."""
    if experts is None:
        return lp
    return dict(lp, moe=dict(lp["moe"], experts=experts, layer=li))


def kv_emit_dict(rows) -> dict:
    """K/V row emission dict from a layer hook's ``rows`` tuple.

    2-tuple (k, v) for plain caches; 4-tuple (k, v, k_scale, v_scale) for
    scaled storage formats (core/kv_format.py) — the scales ride the emit
    pytree so the driver's single arena scatter writes them with the rows.
    """
    d = {"k": rows[0], "v": rows[1]}
    if len(rows) == 4:
        d["k_scale"] = rows[2]
        d["v_scale"] = rows[3]
    return d


def _dense_layer_chunk_emit(p, cfg, x, kv_l, positions, start, *,
                            window=None, rules=RULES):
    """Hook adapter: dense chunk layer -> {"k","v"[,scales]} emission."""
    x, rows = dense_layer_chunk(p, cfg, x, kv_l, positions, start,
                                window=window, rules=rules)
    return x, kv_emit_dict(rows)


def _dense_layer_decode_emit(p, cfg, x_t, kv, li, pos, *, window=None,
                             rules=RULES):
    """Hook adapter: dense decode layer -> {"k","v"[,scales]} emission."""
    x_t, rows = dense_layer_decode_rows(p, cfg, x_t, kv, li, pos,
                                        window=window, rules=rules)
    return x_t, kv_emit_dict(rows)


def dense_chunk_scatter(cache, emits, slot, start):
    """Write one chunk's K/V rows into slot ``slot`` of the arena.

    ``emits``: the layer scan's ys — {"k","v"} of (L, 1, C, KVH, hd), plus
    {"k_scale","v_scale"} of (L, 1, C, KVH) for scaled formats (the same
    three leading index dims, so one scatter expression covers both).  The
    write is a single scatter per leaf at rows [start, start + C) of the
    slot, each row indexed by (layer, slot, row) as the decode scatter
    indexes its rows, which lowers in place under buffer donation: with
    the layer axis left as a window, the v5e compiler re-lays out a
    4-KV-head arena around the scatter (two copies of each leaf per
    chunk).  Scatter (not
    ``dynamic_update_slice``) deliberately: an out-of-range ``slot`` (a
    parked/sentinel index ≥ the slot count) is *dropped* by XLA scatter
    semantics, where dynamic_update_slice would clamp it onto the last
    live slot's rows and corrupt them.
    """
    nl, _, c = emits["k"].shape[:3]
    li = jnp.broadcast_to(jnp.arange(nl)[:, None], (nl, c))
    pi = jnp.broadcast_to(start + jnp.arange(c)[None, :], (nl, c))
    return {key: cache[key].at[li, slot, pi].set(
                emits[key][:, 0].astype(cache[key].dtype))
            for key in emits}


def dense_rows_scatter(cache, emits, pos):
    """Scatter one decode step's K/V rows — ``emits`` {"k","v"} of
    (L, B, KVH, hd), plus {"k_scale","v_scale"} of (L, B, KVH) for scaled
    formats — into each slot's ``pos`` column: the arena's only write this
    step (in place under donation).  A parked slot (pos = PARKED_POS,
    mid-chunked-prefill) scatters out of bounds and is dropped."""
    nl, b = emits["k"].shape[:2]
    li = jnp.broadcast_to(jnp.arange(nl)[:, None], (nl, b))
    bi = jnp.broadcast_to(jnp.arange(b)[None, :], (nl, b))
    pi = jnp.broadcast_to(pos[None, :], (nl, b))
    return {key: cache[key].at[li, bi, pi].set(
                emits[key].astype(cache[key].dtype))
            for key in emits}


def attention_prefill(p_attn, cfg, h, cache_kv, positions, *, window=None,
                      rules=RULES):
    """Causal full-sequence attention + KV-cache fill (shared by the dense/
    moe/hybrid prefill layers).  h: (B, S, d); cache_kv: {"k","v"} of
    (B, Smax, KVH, hd).  Returns (attn_out, new_cache_kv)."""
    from repro.kernels import ops
    q, k, v = L._project_qkv(p_attn, cfg, h, positions, rules)
    b, s, nkv, hd = k.shape
    group = cfg.n_heads // nkv
    # 4-D (B, H, S, hd) with heads separate — see layers.attention
    kf = jnp.repeat(k, group, axis=2).transpose(0, 2, 1, 3)
    vf = jnp.repeat(v, group, axis=2).transpose(0, 2, 1, 3)
    qf = q.transpose(0, 2, 1, 3)
    qf = lanes.constrain(qf, rules, "batch", "heads", None, None)
    kf = lanes.constrain(kf, rules, "batch", "heads", None, None)
    vf = lanes.constrain(vf, rules, "batch", "heads", None, None)
    of = ops.attention(qf, kf, vf, causal=True, window=window,
                       impl="naive")   # no bwd in prefill: kv-outer wins
    o = of.transpose(0, 2, 1, 3)
    out = L._dot(o.reshape(b, s, -1), p_attn["wo"], cfg.adtype)
    if "k_scale" in cache_kv:
        # quantize-on-write: monolithic prefill attends the fresh full-
        # precision K/V above; only the arena copy is narrowed
        fmt = kv_format_mod.get(L.kv_cache_format(cache_kv))
        kq, ks = kv_format_mod.quantize(fmt, k)
        vq, vs = kv_format_mod.quantize(fmt, v)
        new_kv = {
            "k": lax.dynamic_update_slice(cache_kv["k"], kq, (0, 0, 0, 0)),
            "v": lax.dynamic_update_slice(cache_kv["v"], vq, (0, 0, 0, 0)),
            "k_scale": lax.dynamic_update_slice(
                cache_kv["k_scale"], ks, (0, 0, 0)),
            "v_scale": lax.dynamic_update_slice(
                cache_kv["v_scale"], vs, (0, 0, 0)),
        }
        return out, new_kv
    new_kv = {
        "k": lax.dynamic_update_slice(
            cache_kv["k"], k.astype(cache_kv["k"].dtype), (0, 0, 0, 0)),
        "v": lax.dynamic_update_slice(
            cache_kv["v"], v.astype(cache_kv["v"].dtype), (0, 0, 0, 0)),
    }
    return out, new_kv


# ---------------------------------------------------------------------------
# generic stack
# ---------------------------------------------------------------------------

def stack_init(key, cfg, layer_init: Callable) -> Any:
    keys = jax.random.split(key, cfg.n_layers)
    return jax.vmap(lambda k: layer_init(k, cfg))(keys)


def stack_forward(stacked, cfg, x, *, layer_apply: Callable,
                  remat: str = "full", layer_xs: Any = None):
    """scan the layer body over stacked params; returns (x, aux_sum)."""

    def block(carry, inp):
        x, aux = carry
        if layer_xs is None:
            lp, extra = inp, None
        else:
            lp, extra = inp
        x, a = layer_apply(lp, cfg, x, extra)
        return (x, aux + a), None

    body = _maybe_remat(block, remat)
    xs = stacked if layer_xs is None else (stacked, layer_xs)
    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, aux


# ---------------------------------------------------------------------------
# LM drivers (shared by dense / moe / ssm / hybrid; encdec overrides parts)
# ---------------------------------------------------------------------------

class LM:
    """A decoder-only LM family: init/loss/prefill/decode built from a
    layer implementation.

    The serving hot path is family-pluggable through four hooks that share
    one contract — *the arena never rides the layer scan* (XLA's while-loop
    copy insertion would clone it every layer); the scan reads the arena
    (per-layer slot views when chunking, the stacked arena by layer index
    when decoding) and emits only what changed, and the driver writes the
    resident arena exactly once per call:

      * ``layer_chunk(lp, cfg, x, view_l, positions, start, nvalid, extra)``
        — one prompt chunk through one layer against a read-only slot view;
        returns ``(x, emit_l)`` where ``emit_l`` is the layer's chunk
        emission (K/V rows for attention caches, the threaded recurrent
        state for SSD caches).
      * ``chunk_scatter(cache, emits, slot, start)`` — write all layers'
        chunk emissions into slot ``slot`` of the arena (one scatter per
        leaf, in place under donation).
      * ``layer_decode_rows(lp, cfg, x_t, cache, li, pos, extra)`` — one
        decode step of layer ``li`` against the read-only *stacked* cache
        (K/V leaves are read in place by flash-decode; a hook slices
        what else it needs, e.g. recurrent state, with
        ``layers.layer_view``); returns ``(x_t, emit_l)`` (the token's
        K/V rows / the layer's new state).
      * ``rows_scatter(cache, emits, pos)`` — write all layers' decode
        emissions into the arena at ``pos`` (parked slots —
        ``pos == layers.PARKED_POS`` — must be left untouched).

    Dense KV caches get the default implementations; moe/ssm/hybrid plug
    in their own (see the family modules + models/registry.py).
    """

    def __init__(self, cfg, *, layer_init=dense_layer_init,
                 layer_apply=None, init_layer_cache=None, layer_xs_fn=None,
                 layer_chunk=None, chunk_scatter=None,
                 layer_decode_rows=None, rows_scatter=None, rules=RULES):
        self.cfg = cfg
        self.rules = rules
        self._layer_init = layer_init
        self._layer_apply = layer_apply or (
            lambda p, c, x, extra, **kw: dense_layer_apply(
                p, c, x, positions=kw["positions"], rules=self.rules))
        self._init_layer_cache = init_layer_cache or (
            lambda cfg, batch, max_seq, kv_format="fp32":
                L.init_kv_cache(cfg, batch, max_seq, kv_format=kv_format))
        # storage-format capability: a family opts into quantized arenas by
        # accepting ``kv_format`` in its layer-cache constructor.  Families
        # with recurrent state (ssm/hybrid) deliberately do not — state
        # error compounds through the recurrence — so non-fp32 requests
        # fail loudly at init_cache instead of silently storing junk.
        self._kv_format_capable = init_layer_cache is None or (
            "kv_format" in inspect.signature(init_layer_cache).parameters)
        # the arena storage format this model object currently serves;
        # set by init_cache and keyed into every compiled-step cache
        # (engine._per_model) so mixed fleets never share executables
        self.kv_format = "fp32"
        # per-layer static side inputs (e.g. hymba window schedule): (L,) arrays
        self._layer_xs_fn = layer_xs_fn
        # serving hooks: dense defaults for pure-KV caches (``extra`` is the
        # per-layer window where a schedule exists, None otherwise)
        if layer_init is dense_layer_init and layer_chunk is None:
            layer_chunk = (
                lambda lp, c, x, kv_l, positions, start, nvalid, extra:
                    _dense_layer_chunk_emit(lp, c, x, kv_l, positions, start,
                                            window=extra, rules=self.rules))
            chunk_scatter = dense_chunk_scatter
        if layer_init is dense_layer_init and layer_decode_rows is None:
            layer_decode_rows = (
                lambda lp, c, x_t, kv, li, pos, extra:
                    _dense_layer_decode_emit(lp, c, x_t, kv, li, pos,
                                             window=extra, rules=self.rules))
            rows_scatter = dense_rows_scatter
        self._layer_chunk = layer_chunk
        self._chunk_scatter = chunk_scatter
        # names of the step counters the layer hooks emit under ``COUNTS``
        # (empty: the family counts nothing and its steps return none)
        self.counters: tuple = ()
        self._layer_decode_rows = layer_decode_rows
        self._rows_scatter = rows_scatter
        # per-family serving capabilities: chunked (stripmined) prefill and
        # the in-place arena decode path.  Every LM family provides both
        # (dense/moe KV rows, ssm state threading, hybrid's pair) — the
        # flags stay because the serving engine's chunk scheduler and
        # auto-donation policy key off them, and non-LM drivers (encdec)
        # may lack the hooks.
        self.supports_chunked_prefill = (self._layer_chunk is not None
                                         and self._chunk_scatter is not None)
        self.inplace_arena_decode = (self._layer_decode_rows is not None
                                     and self._rows_scatter is not None)
        # prefix sharing composes the chunk path (fork ingestion resumes at
        # the divergence boundary) with the arena decode path (the share
        # view reads donor rows in place) — it needs both hook sets
        self.supports_prefix_sharing = (self.supports_chunked_prefill
                                        and self.inplace_arena_decode)

    # -- params ------------------------------------------------------------
    def init(self, key) -> dict:
        cfg = self.cfg
        ke, kl, kh = jax.random.split(key, 3)
        params = {
            "embed": L.embed_init(ke, cfg.vocab, cfg.d_model, cfg.pdtype),
            "layers": stack_init(kl, cfg, self._layer_init),
            "final_norm": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L.embed_init(
                kh, cfg.vocab, cfg.d_model, cfg.pdtype).T
        return params

    def head(self, params) -> jax.Array:
        return params["lm_head"] if not self.cfg.tie_embeddings \
            else params["embed"].T

    # -- forward -----------------------------------------------------------
    def hidden_states(self, params, tokens, *, prefix_embeds=None,
                      remat: str = "full"):
        cfg = self.cfg
        x = L.embed_lookup(params["embed"], tokens, self.rules)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        layer_apply = functools.partial(self._apply_with_pos,
                                        positions=positions)
        layer_xs = self._layer_xs_fn(cfg) if self._layer_xs_fn else None
        x, aux = stack_forward(params["layers"], cfg, x,
                               layer_apply=layer_apply, remat=remat,
                               layer_xs=layer_xs)
        return L.rmsnorm(params["final_norm"], x, cfg.rms_eps), aux

    def _apply_with_pos(self, p, cfg, x, extra, *, positions):
        return self._layer_apply(p, cfg, x, extra, positions=positions)

    # -- training loss -------------------------------------------------------
    def loss_fn(self, params, batch, *, remat: str = "full",
                ce_block: int = 512):
        """batch: {"tokens": (B,S), "labels": (B,S), "loss_mask": opt}."""
        prefix = batch.get("prefix_embeds")
        h, aux = self.hidden_states(params, batch["tokens"],
                                    prefix_embeds=prefix, remat=remat)
        if prefix is not None:
            h = h[:, prefix.shape[1]:]
        mask = batch.get("loss_mask")
        ce = L.blockwise_cross_entropy(self.head(params), h, batch["labels"],
                                       mask, block=ce_block, rules=self.rules)
        return ce + aux, {"ce": ce, "aux": aux}

    # -- serving -------------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int,
                   kv_format: str = "fp32"):
        """Stacked per-layer caches (leading axis = layer).

        ``kv_format`` selects the arena storage format (core/kv_format.py);
        families whose layer-cache constructor doesn't accept it (recurrent
        state) reject non-fp32 formats.  The chosen format becomes the
        model's current serving format (``self.kv_format``) — one model
        object serves one format at a time; the engine keys its compiled
        steps on it.
        """
        cfg = self.cfg
        kv_format_mod.get(kv_format)          # validate against this build
        if kv_format != "fp32" and not self._kv_format_capable:
            raise ValueError(
                f"family cache {self._init_layer_cache!r} does not support "
                f"kv_format={kv_format!r}: recurrent/custom state stays "
                f"full-precision (see serving README format matrix)")
        self.kv_format = kv_format
        one = self._layer_cache_for(batch, max_seq)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers, *a.shape)), one)

    def _layer_cache_for(self, batch: int, max_seq: int):
        """One per-layer cache in the model's current storage format."""
        if self._kv_format_capable:
            return self._init_layer_cache(self.cfg, batch, max_seq,
                                          kv_format=self.kv_format)
        return self._init_layer_cache(self.cfg, batch, max_seq)

    def prefill(self, params, tokens, cache, *, remat: str = "full"):
        """Run the prompt, fill the cache, return last-position logits.

        Implemented as hidden-state forward + a full-sequence KV write (the
        jnp path reuses blockwise attention; the cache write is a single
        dynamic_update_slice per layer).
        """
        cfg = self.cfg
        b, s = tokens.shape
        x = L.embed_lookup(params["embed"], tokens, self.rules)
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        layer_xs = self._layer_xs_fn(cfg) if self._layer_xs_fn else None

        def block(carry, inp):
            x = carry
            if layer_xs is None:
                lp, cache_l = inp
                extra = None
            else:
                lp, cache_l, extra = inp
            x, cache_l = self._prefill_layer(lp, cfg, x, cache_l, positions,
                                             extra)
            return x, cache_l

        xs = (params["layers"], cache) if layer_xs is None \
            else (params["layers"], cache, layer_xs)
        x, new_cache = lax.scan(block, x, xs)
        h = L.rmsnorm(params["final_norm"], x, cfg.rms_eps)
        last = h[:, -1]
        logits = jnp.dot(last, self.head(params),
                         preferred_element_type=jnp.float32)
        logits = lanes.constrain(logits, self.rules, "batch", "vocab_tp")
        return logits, new_cache

    def _cache_factors(self):
        """Per-leaf batch factor of the family cache pytree (leaf dim 1 is
        batch × factor: 1 for KV/conv leaves, n_heads for fused SSD state).
        Read off an abstract batch=1 layer cache; memoised per model and
        storage format (scaled formats add sidecar leaves)."""
        memo = self.__dict__.setdefault("_cache_factors_memo", {})
        factors = memo.get(self.kv_format)
        if factors is None:
            one = jax.eval_shape(lambda: self._layer_cache_for(1, 8))
            factors = jax.tree.map(lambda leaf: leaf.shape[0], one)
            memo[self.kv_format] = factors
        return factors

    def _seq_axes(self):
        """Per-leaf sequence-axis index of the family cache pytree, or -1
        for leaves with no sequence axis (recurrent state: SSD state /
        conv tail).  Detected structurally — the axis whose extent tracks
        ``max_seq`` across two abstract instantiations — so family modules
        never have to declare it.  Indices are for the *per-layer* leaf
        (the stacked arena leaf's axis is one higher); memoised per model
        and storage format.
        """
        memo = self.__dict__.setdefault("_seq_axes_memo", {})
        axes = memo.get(self.kv_format)
        if axes is None:
            small = jax.eval_shape(lambda: self._layer_cache_for(1, 8))
            big = jax.eval_shape(lambda: self._layer_cache_for(1, 16))

            def ax(ls, lb):
                diff = [i for i, (p, q) in enumerate(zip(ls.shape, lb.shape))
                        if p != q]
                return diff[0] if diff else -1
            axes = jax.tree.map(ax, small, big)
            memo[self.kv_format] = axes
        return axes

    @property
    def has_recurrent_state(self) -> bool:
        """True if any cache leaf carries per-slot recurrent state (no
        sequence axis) — those leaves cannot be shared positionally, so
        prefix-sharing forks need a state snapshot at the divergence
        boundary (see :meth:`extract_slot_state`)."""
        return any(ax < 0 for ax in jax.tree.leaves(self._seq_axes()))

    def _share_view(self, cache, share_src, share_len):
        """Composed read view of the arena under prefix sharing.

        ``share_src``/``share_len``: (B,) int32 — slot b reads sequence
        rows [0, share_len[b]) from slot ``share_src[b]``'s region (the
        donor's shared prefix pages) and its own rows past that.  Leaves
        with no sequence axis (recurrent state) pass through untouched:
        their shared-prefix contribution was spliced into the slot's own
        state at fork time.  An unshared slot has ``share_src[b] == b``
        and ``share_len[b] == 0``, so the select is the identity and the
        composed view is bit-identical to the raw arena — one executable
        serves shared and unshared traffic.

        This is a *read* view only.  The write side (``rows_scatter`` /
        ``chunk_scatter``) always targets the slot's own region, and every
        write position is ≥ the slot's shared length (decode rows sit past
        the prompt; fork chunk cursors start at the divergence boundary),
        so a shared page is never written in place — copy-on-write by
        construction.
        """
        factors = self._cache_factors()

        def comp(leaf, f, ax):
            if ax < 0:
                return leaf
            rows = (share_src[:, None] * f
                    + jnp.arange(f)[None, :]).reshape(-1)
            donor = jnp.take(leaf, rows, axis=1)
            ln = jnp.repeat(share_len, f)
            bshape = [1] * leaf.ndim
            bshape[1] = ln.shape[0]
            tshape = [1] * leaf.ndim
            tshape[ax + 1] = leaf.shape[ax + 1]
            t = jnp.arange(leaf.shape[ax + 1]).reshape(tshape)
            return jnp.where(t < ln.reshape(bshape), donor, leaf)
        return jax.tree.map(comp, cache, factors, self._seq_axes())

    def _share_slot_view(self, cache, slot, share_src, share_len):
        """Slot-view twin of :meth:`_share_view` for the chunk-prefill
        path: one slot's (L, f, ...) view reading sequence rows
        [0, share_len) from the donor slot's region.  ``share_src`` /
        ``share_len`` are traced scalars."""
        own = self._slot_view(cache, slot)
        donor = self._slot_view(cache, share_src)

        def comp(o, d, ax):
            if ax < 0:
                return o
            tshape = [1] * o.ndim
            tshape[ax + 1] = o.shape[ax + 1]
            t = jnp.arange(o.shape[ax + 1]).reshape(tshape)
            return jnp.where(t < share_len, d, o)
        return jax.tree.map(comp, own, donor, self._seq_axes())

    def extract_slot_state(self, cache, slot) -> list:
        """Snapshot one slot's recurrent-state leaves (those without a
        sequence axis), as a flat list in cache-leaf order.  Position-
        addressed leaves are skipped — their rows are shared directly by
        the composed view.  Used by the serving engine to checkpoint a
        prefix donor's SSD state at page boundaries so a later fork can
        resume the recurrence from the divergence point."""
        factors = jax.tree.leaves(self._cache_factors())
        axes = jax.tree.leaves(self._seq_axes())
        out = []
        for leaf, f, ax in zip(jax.tree.leaves(cache), factors, axes):
            if ax >= 0:
                continue
            nslots = leaf.shape[1] // f
            s = jnp.minimum(slot, nslots - 1) * f
            out.append(lax.dynamic_slice(
                leaf, (0, s) + (0,) * (leaf.ndim - 2),
                (leaf.shape[0], f) + leaf.shape[2:]))
        return out

    def splice_slot_state(self, cache, state: list, slot):
        """Inverse of :meth:`extract_slot_state`: write a snapshot into
        slot ``slot``'s recurrent-state rows (drop-on-OOB scatter, same
        discipline as the family scatters).  Position-addressed leaves
        pass through."""
        leaves, treedef = jax.tree.flatten(cache)
        factors = jax.tree.leaves(self._cache_factors())
        axes = jax.tree.leaves(self._seq_axes())
        it = iter(state)
        new = []
        for leaf, f, ax in zip(leaves, factors, axes):
            if ax >= 0:
                new.append(leaf)
                continue
            piece = next(it)
            idx = slot * f + jnp.arange(f)
            new.append(leaf.at[:, idx].set(piece.astype(leaf.dtype)))
        return jax.tree.unflatten(treedef, new)

    def _slot_view(self, cache, slot):
        """Read-only view of one slot's rows across all layers: leaf
        (L, nslots·f, ...) -> (L, f, ...) at slot index ``slot`` (traced),
        with the per-leaf batch factor f applied (dense KV leaves have
        f = 1, fused SSD state leaves f = n_heads).

        The slot index is clamped *explicitly* to the live slot range:
        ``dynamic_slice`` would silently clamp an out-of-range start the
        same way, but the write side (``chunk_scatter``) uses drop-on-OOB
        scatters, and relying on two different OOB behaviours for the same
        sentinel invites exactly the aliasing bug this guards against — a
        parked slot index (≥ nslots) must never *write* the last live
        slot's rows; the clamped read is harmless (its output is
        discarded along with the dropped write)."""
        factors = self._cache_factors()

        def view(leaf, f):
            nslots = leaf.shape[1] // f
            s = jnp.minimum(slot, nslots - 1) * f
            return lax.dynamic_slice(
                leaf, (0, s) + (0,) * (leaf.ndim - 2),
                (leaf.shape[0], f) + leaf.shape[2:])
        return jax.tree.map(view, cache, factors)

    def prefill_chunk(self, params, tokens, cache, slot, start, last_idx,
                      share_src=None, share_len=None, with_counts=False):
        """Stripmined prefill: ingest one prompt chunk straight into slot
        ``slot`` of the resident cache arena.

        tokens: (B=1, C) — one bucket-sized chunk (the final chunk may
        carry right-padding; pad K/V rows land beyond the prompt and are
        overwritten by decode before ever being attended, and recurrent
        families mask pad positions out of their state recurrence).
        ``cache`` is the *full* slot arena (attention leaves
        (L, max_slots, Smax, ...), fused SSD state leaves
        (L, max_slots·nh, N, P)); ``slot`` selects the row being ingested.
        ``start``: scalar int32 — the slot's rows [0, start) are already
        live; this chunk occupies rows [start, start + C).  ``last_idx``:
        scalar int32 index of the chunk's final *real* (non-pad) token —
        C - 1 on every chunk except the last, where padding may pull it
        forward; recurrent-state families thread ``nvalid = last_idx + 1``
        through the layer hook so pad tokens never perturb the carried
        state, and the final chunk's logits are read at ``last_idx``
        (earlier chunks' logits are discarded by the caller).  Returns
        (logits (B, V), new_cache).

        Zero-copy discipline: the layer scan reads the slot through one
        dynamic-slice view (``_slot_view``) and emits only what the chunk
        changed (K/V rows; for SSD layers the threaded (nh, N, P) state +
        conv tail — the chunk recurrence's carry-out); the arena is
        written exactly once, after the scan, by the family's
        ``chunk_scatter``.  Under buffer donation that write lowers in
        place, so the bytes copied per chunk are O(chunk rows) for
        attention caches and O(slot state) for recurrent ones — never
        O(arena), and independent of the slot count.  The arena never
        enters the scan carry: XLA's while-loop copy insertion would
        otherwise clone it every layer.  ``slot``, ``start`` and
        ``last_idx`` are all traced, so one compiled entry serves every
        chunk of every prompt — compile count is bounded by the bucket set.

        ``share_src``/``share_len`` (traced scalars, optional): prefix
        sharing — the slot reads rows [0, share_len) from slot
        ``share_src``'s region (see :meth:`_share_slot_view`).  A forked
        request's chunks all start at ``start >= share_len``, so the
        scatter below still only ever writes the slot's own private rows.
        ``None`` (the default) keeps the original executable untouched.

        ``with_counts``: also return the step counters the layers emit
        (``self.counters``; None when the family counts nothing).
        """
        if not self.supports_chunked_prefill:
            raise NotImplementedError(
                f"chunked prefill not supported for family "
                f"{self.cfg.family!r}")
        h, new_cache, counts = self._chunk_hidden(
            params, tokens, cache, slot, start, last_idx + 1,
            share_src=share_src, share_len=share_len)
        last = lax.dynamic_slice_in_dim(h, last_idx, 1, axis=1)[:, 0]
        logits = jnp.dot(last, self.head(params),
                         preferred_element_type=jnp.float32)
        logits = lanes.constrain(logits, self.rules, "batch", "vocab_tp")
        if with_counts:
            return logits, new_cache, counts
        return logits, new_cache

    def _chunk_hidden(self, params, tokens, cache, slot, start, nvalid,
                      share_src=None, share_len=None):
        """Shared chunk-scan body of :meth:`prefill_chunk` and
        :meth:`verify_chunk`: embed the chunk, run every layer's chunk hook
        against the slot's read-only arena view, write the emissions back
        with one ``chunk_scatter``, and return the final-norm hidden states
        for *all* C rows (the caller picks which rows become logits), and
        the layers' step counters (None when the family counts nothing)."""
        cfg = self.cfg
        b, c = tokens.shape
        x = L.embed_lookup(params["embed"], tokens, self.rules)
        positions = jnp.broadcast_to(start + jnp.arange(c), (b, c))
        layer_xs = self._layer_xs_fn(cfg) if self._layer_xs_fn else None
        if share_src is None:
            slot_view = self._slot_view(cache, slot)
        else:
            slot_view = self._share_slot_view(cache, slot, share_src,
                                              share_len)

        layers, experts = _split_resident(params["layers"])

        def block(carry, inp):
            x = carry
            if layer_xs is None:
                lp, li, view_l = inp
                extra = None
            else:
                lp, li, view_l, extra = inp
            x, emit = self._layer_chunk(_with_resident(lp, experts, li), cfg,
                                        x, view_l, positions, start, nvalid,
                                        extra)
            return x, emit

        # the layer index only where the expert stack is read by it
        layer_ids = (None if experts is None
                     else jnp.arange(cfg.n_layers, dtype=jnp.int32))
        xs = (layers, layer_ids, slot_view) if layer_xs is None \
            else (layers, layer_ids, slot_view, layer_xs)
        x, emits = lax.scan(block, x, xs)
        emits, counts = _split_counts(emits)
        new_cache = self._chunk_scatter(cache, emits, slot, start)
        return (L.rmsnorm(params["final_norm"], x, cfg.rms_eps), new_cache,
                counts)

    def verify_chunk(self, params, tokens, cache, slot, start):
        """Speculative-verify driver: run C already-proposed tokens through
        slot ``slot`` exactly like a prompt chunk, but emit the logits of
        *every* row — row j (predicting absolute position ``start + 1 + j``)
        is what the target model would have produced decoding that position
        one token at a time, bit-identically: the chunk path and the decode
        path share the same blockwise online-softmax attention over the
        same mask set (``ops.flash_prefill_chunk`` row j at q-position
        ``start + j`` attends exactly the keys ``ops.flash_decode`` at
        ``pos = start + j`` does), so the verify pass *is* a replay of k
        sequential decode steps at chunk cost.

        tokens: (B=1, C) — the slot's current token followed by the first
        C-1 draft proposals; never padded, so ``nvalid = C``.  The chunk's
        K/V rows are scattered into rows [start, start + C) of the slot —
        rows past the accepted prefix hold rejected-token K/V, which is
        dead by construction: the next round's chunk starts at the rewound
        position and overwrites them before any row past ``pos`` is ever
        attended (causal masking reads only rows < the query position, and
        committable positions are bounded by the scheduler's
        prompt+max_new admission check).  Rollback therefore costs nothing
        on device — it is the host rewinding its position cursor.

        Returns (logits (B, C, V) f32, new_cache).
        """
        if not self.supports_chunked_prefill:
            raise NotImplementedError(
                f"speculative verify not supported for family "
                f"{self.cfg.family!r} (needs the chunked-prefill hooks)")
        b, c = tokens.shape
        h, new_cache, _ = self._chunk_hidden(params, tokens, cache, slot,
                                             start, jnp.int32(c))
        logits = jnp.dot(h, self.head(params),
                         preferred_element_type=jnp.float32)
        logits = lanes.constrain(logits, self.rules, "batch", None,
                                 "vocab_tp")
        return logits, new_cache

    def _prefill_layer(self, lp, cfg, x, cache_l, positions, extra):
        """Default dense prefill: run layer, stash K/V into the cache."""
        h = L.rmsnorm(lp["ln1"], x, cfg.rms_eps)
        a, cache_l = attention_prefill(
            lp["attn"], cfg, h, cache_l, positions,
            window=self._extra_window(extra), rules=self.rules)
        x = x + a
        h2 = L.rmsnorm(lp["ln2"], x, cfg.rms_eps)
        x = x + L.mlp(lp["mlp"], cfg, h2, rules=self.rules)
        return x, cache_l

    @staticmethod
    def _extra_window(extra):
        return None if extra is None else extra

    def decode_step(self, params, token_t, cache, pos, share=None,
                    with_counts=False):
        """token_t: (B,) int32; pos: (B,) position to write. Returns
        (logits (B,V), new_cache).

        Every LM family takes the arena path: the layer scan reads each
        layer's cache slice and emits only what the token changed (K/V
        rows for attention caches, the layer's new recurrent state for SSD
        caches); the arena is written once, after the scan, by the
        family's ``rows_scatter`` — in place under buffer donation, never
        a re-materialised arena riding the scan carry.

        ``share`` (optional): ``(share_src, share_len)`` (B,) int32
        prefix-sharing vectors — the scan *reads* through the composed
        view (:meth:`_share_view`) while ``rows_scatter`` still writes the
        raw arena, so shared prefix rows are read in place from the donor
        slot and never written.

        ``with_counts``: also return the step counters the layers emit
        (``self.counters``; None when the family counts nothing).
        """
        cfg = self.cfg
        x_t = L.embed_lookup(params["embed"], token_t[:, None],
                             self.rules)[:, 0]
        layer_xs = self._layer_xs_fn(cfg) if self._layer_xs_fn else None
        x_t, new_cache, counts = self._decode_rows(params, cfg, x_t, cache,
                                                   pos, layer_xs, share=share)
        h = L.rmsnorm(params["final_norm"], x_t, cfg.rms_eps)
        logits = jnp.dot(h, self.head(params),
                         preferred_element_type=jnp.float32)
        logits = lanes.constrain(logits, self.rules, "batch", "vocab_tp")
        if with_counts:
            return logits, new_cache, counts
        return logits, new_cache

    def decode_and_sample(self, params, token_t, cache, pos, samp,
                          share=None, with_flags=False, with_counts=False):
        """One decode step + on-device sampling: the serving engine's
        compiled step body, shared by every LM family (all on the
        rows/arena decode path via their ``layer_decode_rows`` /
        ``rows_scatter`` hooks).

        ``samp``: the engine's per-slot sampling vectors — ``{"temp",
        "top_p", "min_p"}`` (B,) f32 and ``{"top_k", "seed"}`` (B,) i32.
        The (B, V) logits stay inside the compiled step — only the sampled
        (B,) int32 token vector comes out.  The token sampled here will
        occupy cache row ``pos + 1``, so its PRNG key folds ``(seed,
        pos + 1)`` (see :func:`repro.models.layers.sample_step`): a pure
        function of the request's seed and the absolute position, never of
        batch composition or donation generation.  Slots with
        ``temp <= 0`` take the bit-exact argmax path.

        ``with_flags``: additionally return a (B,) bool per-slot health
        flag — True iff the slot's logits row is entirely finite — as
        ``(tok, ok, new_cache)``.  The serving engine's quarantine path
        reads it off the step's readback to depart a NaN/Inf-poisoned slot
        without ever shipping the (B, V) logits to the host.

        ``with_counts``: the layers' step counters come last (see
        :meth:`decode_step`).
        """
        kw = {"with_counts": True} if with_counts else {}
        logits, new_cache, *counts = self.decode_step(
            params, token_t, cache, pos, share=share, **kw)
        tok = L.sample_step(logits, samp["seed"], pos + 1, samp["temp"],
                            samp["top_k"], samp["top_p"], samp["min_p"])
        out = (tok, new_cache)
        if with_flags:
            out = (tok, jnp.isfinite(logits).all(axis=-1), new_cache)
        return out + tuple(counts)

    def _decode_rows(self, params, cfg, x_t, cache, pos, layer_xs,
                     share=None):
        """Arena decode: scan layers collecting per-layer emissions (K/V
        rows / new recurrent state), then one in-place write of everything
        into the resident arena via the family's ``rows_scatter``.

        The scan carries the layer index, not a slice of the arena: each
        hook gets the whole stacked cache and ``li``, and flash-decode
        reads layer ``li``'s K/V where they lie.  Under prefix sharing the
        scan reads through the composed view but the scatter targets the
        raw arena — shared rows are never written.  Returns the layers'
        step counters third (None when the family counts nothing).
        """
        read = cache if share is None \
            else self._share_view(cache, share[0], share[1])
        layers, experts = _split_resident(params["layers"])

        def block(x_t, inp):
            if layer_xs is None:
                lp, li = inp
                extra = None
            else:
                lp, li, extra = inp
            return self._layer_decode_rows(_with_resident(lp, experts, li),
                                           cfg, x_t, read, li, pos, extra)

        layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        xs = (layers, layer_ids) if layer_xs is None \
            else (layers, layer_ids, layer_xs)
        x_t, emits = lax.scan(block, x_t, xs)
        emits, counts = _split_counts(emits)
        return x_t, self._rows_scatter(cache, emits, pos), counts
