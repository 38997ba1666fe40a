"""Continuous-batching serving engine: dispatcher model C6 at the serving
layer.

The host (the paper's scalar core) runs scheduling, sampling bookkeeping
and admission; the device (the vector unit) runs one compiled decode step
over the whole slot batch.  Three design rules keep the device out of the
host's shadow:

  1. **One compiled step, always the same shape.**  The decode step covers
     all ``max_slots`` slots every time; dead slots are masked (RVV
     tail-undisturbed via core.masking.apply_mask), never re-shaped out —
     reshaping would recompile, the serving analogue of an issue stall.
  2. **Steps flow through a DispatchQueue.**  ``depth`` decode steps stay
     in flight; the host reads the sampled tokens of step *i−depth* while
     the device runs step *i* (the accelerator-port queue).  Retirement and
     admission therefore act on ``depth``-step-old information — the same
     lag a hardware dispatcher has, and harmless: a finished slot decodes a
     few extra masked tokens that the host drops.
  3. **Admission splices, never rebuilds.**  A new request's prompt enters
     the cache arena by async device ops on the *latest* in-flight state,
     so steady-state decode never synchronises.
  4. **One resident arena, mutated in place.**  Every jitted path that
     threads the KV arena — decode step, chunk ingestion, admission splice
     — writes only the rows it changes (chunk rows / one token row per
     slot; the arena never rides a scan carry or ys, where XLA would clone
     it), and *donates* the arena (``donate_argnums``) so XLA overwrites
     the buffer instead of materialising a fresh one per call: the serving
     analogue of Ara keeping vector operands stationary in the lane-sliced
     VRF.  Donation defaults to an arena-size ``"auto"`` policy (see
     ``DONATE_MIN_BYTES``).  The ownership rule is that a donated
     generation of device state is dead the moment the call is issued; the
     only lagged host read (sampled tokens, ``depth`` steps late) goes
     through a separate never-donated readback copy.

Prefill comes in two modes:

  * **monolithic** (``prefill_chunks=None``) — the whole prompt in one
    batch=1 call, compile-cached *per prompt length*; a long prompt stalls
    the decode batch for its full prefill and every new length recompiles.
  * **chunked** (``prefill_chunks=(...)`` bucket sizes) — the paper's
    stripmining discipline applied to prompt ingestion: the prompt is cut
    into bucket-sized chunks (``serving.chunking``), each ingested by one
    ``model.prefill_chunk`` call that appends K/V rows to the slot's arena
    rows in place and attends causally over the already-written prefix.
    Chunks interleave with decode steps under a per-step token budget
    (``prefill_budget``), so time-to-first-token for short requests no
    longer depends on the longest co-resident prompt, and distinct prefill
    compilations are bounded by the bucket count instead of the number of
    prompt lengths in the traffic mix.

Sampling (temperature / top-k / top-p / min-p) runs *inside* the compiled
decode step (``model.decode_and_sample``): the (B, V) logits never leave the
device, and the per-slot PRNG key is recomputed each step as
``fold_in(fold_in(key0, request_seed), position)`` — no key material lives
in (donated) device state, so a slot's token stream is a pure function of
(seed, position), invariant to batch composition, chunked-prefill
interleaving, preemption/recompute and donation generation (see
``serving.sampling``).  Greedy slots take the bit-exact argmax path, and a
step whose RUNNING slots are *all* greedy dispatches a pure-argmax twin
executable (same signature and donation structure) so greedy-only traffic
never pays the sampling transform at all.

Dead slots keep decoding garbage tokens; correctness holds because (a)
flash-decode tail predication hides rows ≥ the slot's live length, (b)
prefill overwrites rows [0, prefill_len) — and a recurrent (SSD) state is
explicitly re-zeroed by the first chunk / overwritten by the monolithic
splice, and (c) a frozen slot's position pointer stops advancing
(pos += active).  A slot that leaves the decode batch, and a slot
undergoing *chunked* prefill, additionally parks its position pointer at
the ``PARKED_POS`` sentinel: flash-decode fetches none of its arena
rows, the decode step's KV
scatter for that row goes out of bounds and is dropped (XLA scatter
semantics), and recurrent-state writes are keep-masked on
``pos < PARKED_POS`` (SSD state is not position-addressed, so the drop
must be explicit) — in-flight decode steps can never corrupt prompt rows
or chunk-threaded state already written by earlier chunks.
"""
from __future__ import annotations

import collections
import functools
import inspect
import time
import warnings
import weakref
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kv_format as kv_format_mod
from repro.core import masking, spans
from repro.core.dispatch import DispatchQueue
from repro.kernels.flash_decode import strip_counts
from repro.models.layers import PARKED_POS
from repro.runtime.serving import chunking, sampling
from repro.runtime.serving.cache import (PagedKVCacheManager, PrefixMatch,
                                         cache_insert)
from repro.runtime.serving.config import EngineConfig
from repro.runtime.serving.faults import FaultInjector
from repro.runtime.serving.health import HealthMonitor, HealthState
from repro.runtime.serving.request import Request, RequestState, Status
from repro.runtime.serving.scheduler import AdmissionRejected, Scheduler
from repro.runtime.serving.speculative import SpecController


# Buffer-donation pay-off threshold.  Donation removes the output-copy of
# every donated buffer (the arena stops being re-materialised per step) but
# costs the runtime fixed per-call ownership bookkeeping, so tiny test/CI
# arenas can run faster undonated while any production-sized arena
# (max_slots·max_seq in the thousands of rows) pays the fixed cost back
# many times over.  ``donate="auto"`` switches on this arena-size
# threshold; the 1 MiB value was tuned on a CPU client and has not been
# measured on a chip.  The structural zero-copy paths (chunk-rows-only
# writes, no extract/insert round-trip) are unconditional — they win at
# every size.
DONATE_MIN_BYTES: int = 1 << 20


def _per_model(build):
    """Compiled step functions are cached per *model object* (and donation
    flag), not per engine — spinning up a fresh engine for the same model
    (benchmarks sweep dispatch depths, tests sweep pool sizes) must hit
    the jit cache, not recompile.  The previous ``functools.lru_cache``
    pinned every model ever served — and the XLA executables compiled for
    it — for process lifetime, so benchmark sweeps leaked compiled
    programs.  A ``WeakKeyDictionary`` alone does not fix that: the cached
    jitted fn *closes over* the model, so the value would keep its own key
    alive.  Instead the compiled fn is memoised on the model instance
    itself (a self-cycle the garbage collector reclaims with the model),
    with a ``WeakValueDictionary`` index kept purely for
    tests/diagnostics.

    The model's current KV storage format is part of the cache key: a
    model re-initialised for a different ``kv_format`` serves a different
    arena pytree, so a fleet mixing formats never silently shares
    executables (jit would retrace on avals anyway; the key makes the
    separation explicit and observable)."""
    name = build.__name__
    index: weakref.WeakValueDictionary = weakref.WeakValueDictionary()

    @functools.wraps(build)
    def get(model, donate: bool = True):
        fmt = getattr(model, "kv_format", "fp32")
        attr = f"_{name}_compiled_{bool(donate)}_{fmt}"
        fn = model.__dict__.get(attr)
        if fn is None:
            fn = build(model, donate)
            setattr(model, attr, fn)
            index[id(model)] = model
        return fn

    get.cache = index          # live models with a compiled entry
    return get


def _counted(model) -> dict:
    """The keyword that makes a family's step also return the counters its
    layers emit (``model.counters``, e.g. MoE's rows routed here); nothing
    for a family that counts none, whose steps stay as they were."""
    return {"with_counts": True} if getattr(model, "counters", ()) else {}


# Ownership discipline for donated device state: the engine owns exactly one
# live generation of (tokens, cache, pos, active); every jitted mutation
# below *donates* those inputs and the engine immediately rebinds its
# references to the outputs, so the arena is updated in place and the
# donated (dead) buffers are never touched again.  The only value read
# host-side after the fact — the sampled-token vector, read ``depth`` steps
# late by ``_drain_pending`` — is returned as a separate never-donated
# readback output (the raw ``sampled`` vector below), because the token
# *state* buffer is donated into the next step while the host's lagged
# read is still pending.

@_per_model
def _compiled_decode(model, donate):
    def step(params, tokens, cache, pos, active, samp):
        # decode + sampling in one compiled body (model.decode_and_sample):
        # the (B, V) logits never leave the device.  ``samp`` is the
        # per-slot sampling state (temp/top_k/top_p/min_p/seed vectors);
        # greedy slots (temp <= 0) take the bit-exact argmax path.  The
        # PRNG key of each draw folds (seed, pos+1) inside the step — no
        # key material lives in device state, so donating ``samp`` (it
        # passes through unchanged, aliased in place) cannot perturb a
        # stream across donation generations.
        sampled, ok, cache, *counts = model.decode_and_sample(
            params, tokens, cache, pos, samp, with_flags=True,
            **_counted(model))
        # dead slots: keep the old token (tail-undisturbed) & freeze pos
        tokens = masking.apply_mask(tokens, sampled, active == 1)
        pos = pos + active
        # the lagged host read gets the *raw* sampled vector: a distinct
        # HLO value from the masked token state, so buffer assignment can
        # never fold it onto the state buffer that is donated into the
        # next step (a value-identical copy like ``tokens + 0`` could be
        # simplified away and end up sharing the doomed buffer).  The
        # drain only consumes entries for slots that were RUNNING at
        # submit (active == 1), where sampled == masked tokens.  ``ok``
        # rides the same readback: a (B,) bool per-slot health flag (the
        # slot's logits row is entirely finite) the drain checks before
        # committing — a NaN/Inf-poisoned slot is quarantined without the
        # (B, V) logits ever leaving the device.  A counting family's step
        # counters ride last, read with the tokens.
        return (tokens, cache, pos, active, samp, sampled, ok, *counts)
    return jax.jit(step, donate_argnums=(1, 2, 3, 4, 5) if donate else ())


@_per_model
def _compiled_decode_greedy(model, donate):
    """The pure-argmax twin of :func:`_compiled_decode` — same signature,
    same donation structure (``samp`` passes through, aliased), no sampling
    transform (sort / softmax / Gumbel).  The engine picks per step: a step
    whose RUNNING slots are all greedy runs this executable, so pure-greedy
    traffic pays exactly the pre-sampling step cost.  Switching executables
    mid-run is safe — both consume/produce the same donated state, and
    tokens for a slot that turns sampled *after* a greedy step was
    submitted are dropped by the engine's slot-generation staleness guard
    (activation bumps the generation)."""
    def step(params, tokens, cache, pos, active, samp):
        logits, cache, *counts = model.decode_step(params, tokens, cache, pos,
                                                   **_counted(model))
        sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ok = jnp.isfinite(logits).all(axis=-1)
        tokens = masking.apply_mask(tokens, sampled, active == 1)
        pos = pos + active
        return (tokens, cache, pos, active, samp, sampled, ok, *counts)
    return jax.jit(step, donate_argnums=(1, 2, 3, 4, 5) if donate else ())


@_per_model
def _compiled_decode_shared(model, donate):
    """Prefix-sharing variant of :func:`_compiled_decode`: the decode
    state gains the per-slot share vectors ``{"src", "len"}`` (donated,
    passed through unchanged like ``samp``), and the layer scan reads the
    arena through the composed share view — slot b's rows
    [0, share_len[b]) come from slot share_src[b]'s region.  An unshared
    slot has src == own slot and len == 0, making the select the
    identity, so one executable serves mixed shared/unshared batches
    bit-identically to the unshared twin."""
    def step(params, tokens, cache, pos, active, samp, share):
        sampled, ok, cache, *counts = model.decode_and_sample(
            params, tokens, cache, pos, samp,
            share=(share["src"], share["len"]), with_flags=True,
            **_counted(model))
        tokens = masking.apply_mask(tokens, sampled, active == 1)
        pos = pos + active
        return (tokens, cache, pos, active, samp, share, sampled, ok,
                *counts)
    return jax.jit(step,
                   donate_argnums=(1, 2, 3, 4, 5, 6) if donate else ())


@_per_model
def _compiled_decode_greedy_shared(model, donate):
    def step(params, tokens, cache, pos, active, samp, share):
        logits, cache, *counts = model.decode_step(
            params, tokens, cache, pos, share=(share["src"], share["len"]),
            **_counted(model))
        sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ok = jnp.isfinite(logits).all(axis=-1)
        tokens = masking.apply_mask(tokens, sampled, active == 1)
        pos = pos + active
        return (tokens, cache, pos, active, samp, share, sampled, ok,
                *counts)
    return jax.jit(step,
                   donate_argnums=(1, 2, 3, 4, 5, 6) if donate else ())


@_per_model
def _compiled_draft_propose(model, donate):
    """One draft micro-step of a speculative round: decode + sample over
    the whole slot batch, exactly the decode-step body but donating ONLY
    the draft arena — tokens/pos are round-local values the engine rebuilds
    from host state, and ``samp`` (the *target's* per-slot sampling
    vectors) is shared across every micro-step and verify call of the
    round, so neither may be consumed.  The draft samples with the same
    (seed, position) key-fold as the target: proposal j+1 draws at
    ``pos + j + 1`` with the slot's seed, the exact key the target's
    Gumbel replay uses at that position — the Gumbel noise is shared and
    only the logits differ (the coupling that makes acceptance approach 1
    as temperature grows)."""
    def step(params, tokens, cache, pos, samp):
        sampled, cache = model.decode_and_sample(params, tokens, cache,
                                                 pos, samp)
        return sampled, cache
    return jax.jit(step, donate_argnums=(2,) if donate else ())


@_per_model
def _compiled_draft_propose_greedy(model, donate):
    """Argmax twin of :func:`_compiled_draft_propose` for rounds whose
    RUNNING slots are all greedy — proposals are the draft's argmax, to be
    matched against the target's argmax."""
    def step(params, tokens, cache, pos, samp):
        del samp
        logits, cache = model.decode_step(params, tokens, cache, pos)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache
    return jax.jit(step, donate_argnums=(2,) if donate else ())


@_per_model
def _compiled_verify(model, donate):
    """The speculative verify step: one chunk-shaped pass over a slot's
    current token + k-1 proposals (``model.verify_chunk``), then the
    Gumbel replay (``sampling.verify_draws``) — the target's deterministic
    draw at every one of the k positions, inside the same executable so
    the (C, V) logits never leave the device.  Donates the target arena
    (the chunk's K/V rows are scattered in place); ``slot``/``start`` are
    traced, so the only compile key is the chunk length C = k — one
    executable per adaptive-k ladder rung."""
    def step(params, cache, tokens, slot, start, samp):
        logits, cache = model.verify_chunk(params, tokens, cache, slot,
                                           start)
        draws = sampling.verify_draws(logits[0], slot, start, samp)
        ok = jnp.isfinite(logits[0]).all()
        return draws, ok, cache
    return jax.jit(step, donate_argnums=(1,) if donate else ())


@_per_model
def _compiled_verify_greedy(model, donate):
    """Argmax twin of :func:`_compiled_verify`: a greedy slot's acceptance
    rule is exact match against the target's argmax at each position, so
    the verify draws are a plain per-row argmax — no sampling transform."""
    def step(params, cache, tokens, slot, start, samp):
        del samp
        logits, cache = model.verify_chunk(params, tokens, cache, slot,
                                           start)
        draws = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)
        ok = jnp.isfinite(logits[0]).all()
        return draws, ok, cache
    return jax.jit(step, donate_argnums=(1,) if donate else ())


@_per_model
def _compiled_prefill(model, donate):
    # the batch=1 zero-cache template is reused by every admission, so it
    # is NOT donated here; the arena splice (_insert_jit) donates instead
    del donate
    return jax.jit(lambda p, t, c, e: model.prefill(p, t, c, **e))


@_per_model
def _compiled_prefill_chunk(model, donate):
    """One chunk straight into the slot arena: ``model.prefill_chunk``
    scatters the chunk's K/V rows into the slot's region of the (donated)
    arena (no extract/insert round-trip — the bytes written are the
    chunk's rows).  ``slot``, ``start`` and ``last_idx`` are traced — the
    only compile key is the chunk length, so compiles are bounded by the
    bucket set.  A counting family's step counters come third."""
    def chunk_step(params, big_cache, tokens, slot, start, last_idx):
        return model.prefill_chunk(params, tokens, big_cache, slot, start,
                                   last_idx, **_counted(model))
    return jax.jit(chunk_step, donate_argnums=(1,) if donate else ())


@_per_model
def _compiled_prefill_chunk_shared(model, donate):
    """Prefix-sharing chunk ingestion: the fork's chunks attend over the
    donor's shared rows through the composed slot view (``share_src`` /
    ``share_len`` traced scalars; a pure slot passes (own slot, 0) and
    gets identical math).  The scatter still writes only the slot's own
    rows — every fork chunk starts at ``start >= share_len``."""
    def chunk_step(params, big_cache, tokens, slot, start, last_idx,
                   share_src, share_len):
        return model.prefill_chunk(params, tokens, big_cache, slot, start,
                                   last_idx, share_src=share_src,
                                   share_len=share_len, **_counted(model))
    return jax.jit(chunk_step, donate_argnums=(1,) if donate else ())


@_per_model
def _compiled_extract_state(model, donate):
    """Snapshot one slot's recurrent-state leaves (never donated — the
    arena stays live; the snapshot is an independent O(slot state) copy
    parked in the prefix index)."""
    del donate
    return jax.jit(lambda cache, slot: model.extract_slot_state(cache, slot))


@_per_model
def _compiled_splice_state(model, donate):
    """Write a parked snapshot into a fork's recurrent-state rows.  The
    arena is donated (in-place row write); the snapshot is not — the same
    snapshot serves every future fork of its prefix."""
    def splice(cache, state, slot):
        return model.splice_slot_state(cache, state, slot)
    return jax.jit(splice, donate_argnums=(0,) if donate else ())


_insert_jit = jax.jit(cache_insert, donate_argnums=0)
_insert_plain_jit = jax.jit(cache_insert)


def _common_prefix_len(a: np.ndarray, b: np.ndarray) -> int:
    n = min(len(a), len(b))
    if n == 0:
        return 0
    neq = np.nonzero(a[:n] != b[:n])[0]
    return int(neq[0]) if neq.size else n


# per-slot state pokes: a few bytes per admission — donation's fixed
# per-call cost would dwarf the copies it elides, so these stay functional
@jax.jit
def _set_slot_jit(tokens, pos, active, slot, token0, pos0):
    return (tokens.at[slot].set(token0),
            pos.at[slot].set(pos0),
            active.at[slot].set(1))


@jax.jit
def _park_slot_jit(pos, slot, sentinel):
    return pos.at[slot].set(sentinel)


@jax.jit
def _retire_slot_jit(pos, active, slot, sentinel):
    return pos.at[slot].set(sentinel), active.at[slot].set(0)


@jax.jit
def _set_share_jit(share, slot, src, ln):
    return {"src": share["src"].at[slot].set(src),
            "len": share["len"].at[slot].set(ln)}


class ServingEngine:
    """Continuous-batching generation over any registry model family.

    ``model`` must expose the driver surface (init_cache / prefill /
    decode_step); ``cfg`` its ArchConfig.  depth=0 degrades to blocking
    dispatch (the paper's worst case) — the mode sweep in
    benchmarks/bench_serving.py measures exactly that gap.

    ``prefill_chunks``: ``None`` for monolithic prefill, or a tuple of
    bucket sizes (e.g. ``chunking.DEFAULT_BUCKETS``) to enable stripmined
    chunked prefill (every LM family — dense/MoE K/V rows, SSM/hybrid
    thread the SSD chunk recurrence through the slot's arena state; see
    ``model.supports_chunked_prefill``).  ``prefill_budget`` caps how many
    prompt
    tokens are ingested per engine step (default: the largest bucket) —
    the knob trading prefill throughput against decode-batch stall time.

    ``donate``: ``"auto"`` (default) donates the KV arena into every step
    once ``arena_bytes >= DONATE_MIN_BYTES`` *and* the model decodes via
    the in-place arena path (``model.inplace_arena_decode``) — in-place
    reuse beats the runtime's fixed per-call donation bookkeeping exactly
    when the buffer is large, which is the regime this engine targets;
    ``True``/``False`` force the choice (tests force ``True`` to pin
    buffer identity).

    ``base_seed``: the run-level PRNG seed.  A sampled request whose
    ``SamplingParams.seed`` is ``None`` uses it, so two engines with the
    same base seed and the same requests generate identical streams; the
    per-draw key folds only (request seed, absolute position) — see
    :mod:`repro.runtime.serving.sampling`.

    ``device`` (optional): the one device this engine runs on.  Params and
    every piece of device state (slot vectors, arena, templates) are
    committed to it at construction, so each compiled step — whose other
    inputs are uncommitted host scalars — executes there.  ``None`` leaves
    placement to JAX's default device.  A router gives each replica its
    own chip this way.

    Construction: ``ServingEngine(model, cfg, params,
    config=EngineConfig(...))`` is the documented path — every knob above
    is an :class:`EngineConfig` field.  Legacy keyword construction
    (``max_slots=...`` etc.) still works for one PR via a deprecation shim
    that warns and builds the config; behavior is identical.
    """

    def __init__(self, model, cfg, params, *,
                 config: Optional[EngineConfig] = None,
                 clock=None, device=None, **legacy):
        # ``clock``: the engine's wall-clock source (default
        # time.perf_counter) — drives submitted_at / ttft / deadlines, so
        # deadline tests inject a fake clock and replay expiries
        # deterministically.
        self._clock = clock if clock is not None else time.perf_counter
        if legacy:
            if config is not None:
                raise TypeError(
                    f"pass either config=EngineConfig(...) or legacy "
                    f"keywords, not both: {sorted(legacy)}")
            warnings.warn(
                "ServingEngine keyword construction (max_slots=..., "
                "prefill_chunks=..., ...) is deprecated; pass "
                "config=EngineConfig(...) instead — same field names, "
                "identical behavior", DeprecationWarning, stacklevel=2)
            config = EngineConfig(**legacy)
        elif config is None:
            config = EngineConfig()
        self.config = config
        self.model = model
        self.cfg = cfg
        self.device = device
        self.params = self._on_device(lambda: params)
        max_slots = self.max_slots = config.max_slots
        max_seq = self.max_seq = config.max_seq
        self.depth = config.depth
        prefill_chunks = config.prefill_chunks
        self.prefix_extra = (cfg.n_patch_tokens
                             if cfg.family == "vlm" else 0)
        if prefill_chunks is not None:
            if not getattr(model, "supports_chunked_prefill", False):
                raise ValueError(
                    f"family {cfg.family!r} does not support chunked "
                    f"prefill; use prefill_chunks=None")
            if self.prefix_extra:
                raise ValueError("chunked prefill with prefix_extra "
                                 "(VLM patch tokens) is unsupported")
        self.prefill_chunks = prefill_chunks
        self.prefill_budget = (config.prefill_budget
                               if config.prefill_budget is not None
                               else (max(prefill_chunks)
                                     if prefill_chunks else 0))
        self.prefix_sharing = bool(config.prefix_sharing)
        if self.prefix_sharing and not getattr(
                model, "supports_prefix_sharing", False):
            raise ValueError(
                f"family {cfg.family!r} does not support prefix sharing "
                f"(needs the chunked-prefill and arena-decode hooks)")
        # fault injection: one seeded injector shared by every site; the
        # cache manager consults it through a narrow callable so cache.py
        # stays decoupled from the injector type
        self._injector = (FaultInjector(config.faults)
                          if config.faults is not None else None)
        # KV storage format (core/kv_format.py): resolved once here, then
        # threaded to the model arena (init_cache), the page accountant
        # (scale-sidecar lifecycle) and the compiled-step cache keys
        self.kv_format = config.kv_format
        fmt = kv_format_mod.get(self.kv_format)
        # drivers whose init_cache predates the format parameter (encdec's
        # cross-attention arena) can only serve the fp32 reference format
        if "kv_format" in inspect.signature(model.init_cache).parameters:
            self._cache_kw = {"kv_format": self.kv_format}
        elif self.kv_format != "fp32":
            raise ValueError(
                f"family {cfg.family!r} does not support kv_format="
                f"{self.kv_format!r}: its cache constructor is fp32-only")
        else:
            self._cache_kw = {}
        self.kv_row_bytes = kv_format_mod.bytes_per_row(
            fmt, getattr(cfg, "n_kv_heads", 1), getattr(cfg, "hd", 0),
            cfg.adtype) * cfg.n_layers
        num_pages = config.num_pages
        if num_pages is None:       # default: pool sized to the full arena
            num_pages = max_slots * -(-max_seq // config.page_size)
        self.cache_mgr = PagedKVCacheManager(
            num_pages, config.page_size,
            max_chains=config.prefix_chain_cap,
            fault=self._cache_fault if self._injector else None,
            kv_format=self.kv_format,
            row_bytes=self.kv_row_bytes)
        self.scheduler = Scheduler(
            max_slots, self.cache_mgr,
            prefix_extra=self.prefix_extra,
            max_len=max_seq,
            chunked=prefill_chunks is not None,
            admission_reclaim_cap=config.admission_reclaim_cap,
            admission_attempt_cap=config.admission_attempt_cap,
            admission_backoff_cap=config.admission_backoff_cap,
            preempt_cap=config.preempt_cap)
        # health ladder: observed once per step off the engine's own
        # counters; its state gates spec/prefill/admission (see health.py)
        self.health = (HealthMonitor(config.health)
                       if config.health is not None else None)

        # device state: the slot batch; a slot no request has used yet is
        # parked like a retired one (it reads and writes no arena row, and
        # routes no row to an expert)
        self._tokens = jnp.zeros((max_slots,), jnp.int32)
        self._pos = jnp.full((max_slots,), PARKED_POS, jnp.int32)
        self._active = jnp.zeros((max_slots,), jnp.int32)
        # the host's copies of pos/active, kept in step with every update
        # the host sends (the decode step's pos += active included)
        self._host_pos = np.full((max_slots,), PARKED_POS, np.int64)
        self._host_active = np.zeros((max_slots,), np.int64)
        # per-slot sampling params (greedy until a sampled admission);
        # threaded through — and donated with — every decode step
        self.base_seed = int(config.base_seed)
        self._samp = sampling.init_slot_state(max_slots)
        # per-slot prefix-share vectors (donated with the decode state):
        # slot b reads rows [0, len[b]) from slot src[b]'s region.  The
        # identity mapping (src == own slot, len == 0) is a no-op share.
        self._share = ({"src": jnp.arange(max_slots, dtype=jnp.int32),
                        "len": jnp.zeros((max_slots,), jnp.int32)}
                       if self.prefix_sharing else None)
        (self._tokens, self._pos, self._active, self._samp, self._share,
         self._cache) = self._on_device(lambda: (
            self._tokens, self._pos, self._active, self._samp, self._share,
            model.init_cache(max_slots, max_seq, **self._cache_kw)))

        self.arena_bytes = sum(
            leaf.nbytes for leaf in jax.tree.leaves(self._cache))
        # donation policy: "auto" donates the arena once it is big enough
        # for in-place reuse to beat the runtime's fixed per-call ownership
        # bookkeeping (DONATE_MIN_BYTES) — and only for models whose decode
        # takes the arena path (per-row in-place writes / state keep-masks).
        # Every LM family (dense/moe/ssm/hybrid/vlm) does since the
        # rows/arena port; the flag guards non-LM drivers that still thread
        # caches functionally.  True/False force the choice.  The
        # structural zero-copy paths are active regardless.
        donate = config.donate
        if donate == "auto":
            donate = (self.arena_bytes >= DONATE_MIN_BYTES
                      and getattr(model, "inplace_arena_decode", False))
        self.donate = bool(donate)
        if self.prefix_sharing:
            self._decode = _compiled_decode_shared(model, self.donate)
            self._decode_greedy = _compiled_decode_greedy_shared(
                model, self.donate)
        else:
            self._decode = _compiled_decode(model, self.donate)
            self._decode_greedy = _compiled_decode_greedy(model, self.donate)
        self._use_sampling = False      # per-step executable choice
        self._insert = _insert_jit if self.donate else _insert_plain_jit
        # one prefill wrapper per model, compile-cached per prompt length
        self._prefill_fn = _compiled_prefill(model)
        # batch=1 zero cache reused by every monolithic admission (purely
        # functional — prefill returns a new cache, this one is never
        # written and never donated)
        self._one_cache = self._on_device(
            lambda: model.init_cache(1, max_seq, **self._cache_kw))
        if prefill_chunks is not None:
            self._chunk_fn = (
                _compiled_prefill_chunk_shared(model, self.donate)
                if self.prefix_sharing
                else _compiled_prefill_chunk(model, self.donate))
        if self.prefix_sharing:
            # recurrent families (SSD state / conv tail) can only fork at
            # boundaries where the donor's state was checkpointed
            self._needs_state_snapshot = bool(
                getattr(model, "has_recurrent_state", False))
            self._extract_state = _compiled_extract_state(model, False)
            self._splice_state = _compiled_splice_state(model, self.donate)
        # speculative decoding: a draft LM in a second slot-major arena
        # sharing the target's slot indices.  Rounds are synchronous (each
        # round's proposals depend on the last round's committed tokens, so
        # the dispatch-queue depth lag cannot apply); the dispatch queue
        # carries only non-speculative traffic.
        self.spec: Optional[SpecController] = None
        if config.speculative is not None:
            if self.prefix_extra:
                raise ValueError("speculative decoding with prefix_extra "
                                 "(VLM patch tokens) is unsupported")
            self.spec = SpecController(cfg, config.speculative)
            dm = self.spec.draft_model
            if not (getattr(model, "supports_chunked_prefill", False)
                    and getattr(model, "inplace_arena_decode", False)
                    and getattr(dm, "inplace_arena_decode", False)
                    and getattr(dm, "supports_chunked_prefill", False)):
                raise ValueError(
                    "speculative decoding needs the chunked-prefill and "
                    "arena-decode hooks on both target and draft")
            self._draft_params, self._draft_cache, self._draft_one_cache = \
                self._on_device(lambda: (
                    jax.jit(dm.init)(
                        jax.random.PRNGKey(config.speculative.draft_seed)),
                    dm.init_cache(max_slots, max_seq),
                    dm.init_cache(1, max_seq)))
            self._draft_prefill_fn = _compiled_prefill(dm)
            if prefill_chunks is not None:
                self._draft_chunk_fn = _compiled_prefill_chunk(
                    dm, self.donate)
            self._draft_propose = _compiled_draft_propose(dm, self.donate)
            self._draft_propose_greedy = _compiled_draft_propose_greedy(
                dm, self.donate)
            self._verify = _compiled_verify(model, self.donate)
            self._verify_greedy = _compiled_verify_greedy(model, self.donate)
            self._verify_shapes: set = set()
        # readback copies of in-flight steps' tokens, with the slot→state
        # map seen at submit; per-slot admission generation guards against
        # crediting a stale in-flight token to a slot that was recycled
        # meanwhile.  (These are the ``read`` outputs — the token *state*
        # buffers themselves are donated into the following step and must
        # never be re-read.)
        self._pending: collections.deque = collections.deque()
        self._slot_gen = [0] * max_slots
        self._results: dict[Any, RequestState] = {}
        # distinct prefill-path compile-cache entries this engine touched:
        # ("prefill", prompt_len) monolithic, ("chunk", size) chunked
        self._prefill_shapes: set = set()
        self._prefill_tick = 0
        # robustness state: the engine's step counter (admission backoff
        # ticks), the per-step fault flag feeding the health monitor's
        # consecutive-faults signal, and the lazily-built NaN template for
        # the logits-poison site
        self._tick = 0
        self._step_faulted = False
        self._deadlines_active = False
        self._nan_one = None
        self._zero_one = None
        self._poisoned_slots: set = set()
        self._spec_resync = False
        self.stats = {"decode_steps": 0, "prefills": 0, "prefill_chunks": 0,
                      "prefill_compiles": 0, "prefill_rows": 0,
                      "tokens_out": 0, "requests": 0,
                      "sampled_requests": 0, "sampled_steps": 0,
                      "forks": 0, "shared_prompt_tokens": 0,
                      "prefix_hits": 0, "prefix_deferrals": 0,
                      "timed_out": 0, "failed": 0, "migrated": 0,
                      "quarantined": 0,
                      "poisoned": 0, "deadline_overrun_s": {},
                      "host_blocked_s": 0.0, "ttft_s": {},
                      "kv_format": self.kv_format,
                      "kv_row_bytes": self.kv_row_bytes,
                      "arena_bytes": self.arena_bytes,
                      # engine steps, and PREFILLING slots summed once per
                      # step after admission (slot-steps held by prefill)
                      "steps": 0, "slot_steps_prefilling": 0,
                      # K/V strips flash-decode fetches per layer, summed
                      # over decode steps
                      "decode_kv_strips": 0}
        # the family's step counters (MoE: rows routed to the experts held
        # here, held experts given a row), summed over layers and steps:
        # every step under its own name, decode steps alone under
        # ``decode_<name>``.  They are read with the tokens of the decode
        # step they rode with (a chunk's with the next decode step's), so
        # reading them adds no wait.
        self._counters = tuple(getattr(model, "counters", ()))
        for name in self._counters:
            self.stats[name] = self.stats["decode_" + name] = 0
        self._chunk_counts: list = []
        # decode-state buffers are donated into each step, so the queue
        # tracks a never-donated readback output (the sampled vector,
        # ahead of the ok-flag readback and any step counters) for
        # backpressure; its blocks count into the engine's host_blocked_s
        lag = 3 if self._counters else 2
        self._queue = DispatchQueue(self._submit_decode, depth=self.depth,
                                    inflight_of=lambda out: out[-lag],
                                    counts=self.stats)
        if self._injector is not None:
            # live view of per-site fire counts (aliased, not copied)
            self.stats["faults"] = self._injector.fired
        if self.health is not None:
            self.stats["health"] = self.health.state.name
            self.stats["health_transitions"] = 0
        if self.spec is not None:
            # speculative counters: rounds = verify rounds (the spec
            # analogue of decode_steps), draft_steps = draft micro-steps,
            # verify_calls = per-slot verify executions, verify_compiles =
            # distinct verify-chunk shapes touched (bounded by the
            # adaptive-k ladder).  Acceptance bookkeeping — per-request
            # accepted/proposed — lives on ``self.spec.stats``.
            self.stats.update({"spec_rounds": 0, "spec_draft_steps": 0,
                               "spec_verify_calls": 0,
                               "spec_verify_compiles": 0})

    def _on_device(self, make):
        """``make()``'s device state, built on this engine's device and
        committed to it (left as built when the engine has none).  Building
        under the device — not on the default device and then copying —
        keeps other replicas' arenas off the default device."""
        if self.device is None:
            return make()
        with jax.default_device(self.device):
            return jax.device_put(make(), self.device)

    def _submit_decode(self, state):
        if self._use_sampling:
            self.stats["sampled_steps"] += 1
            return self._decode(self.params, *state)
        return self._decode_greedy(self.params, *state)

    # -- fault / health plumbing ---------------------------------------------
    def _cache_fault(self, site: str) -> bool:
        """The cache manager's fault hook: delegates to the injector and
        flags the step so the health ladder sees allocation faults."""
        if self._injector.fire(site):
            self._step_faulted = True
            return True
        return False

    @property
    def _health_state(self) -> HealthState:
        return self.health.state if self.health else HealthState.HEALTHY

    def _effective_prefill_budget(self) -> int:
        """The configured budget, shrunk by the ladder at >= SHEDDING."""
        budget = self.prefill_budget
        if (self.health is not None and budget
                and self._health_state >= HealthState.SHEDDING):
            budget = max(1, int(budget
                                * self.health.config.shed_prefill_frac))
        return budget

    def _depart(self, st: RequestState, status: Status,
                reason: str) -> None:
        """Abnormal departure + decode-batch deactivation (the engine half
        of ``Scheduler.depart``)."""
        slot = self.scheduler.depart(st, status, reason)
        if slot is not None:
            self._retire_slot(slot)
        key = {Status.TIMED_OUT: "timed_out",
               Status.MIGRATED: "migrated"}.get(status, "failed")
        self.stats[key] += 1

    def _expire_deadlines(self) -> None:
        """Depart every request whose deadline passed — WAITING and
        resident alike — with TIMED_OUT and its partial output (a clean
        prefix of the fault-free stream).  The overrun is recorded per
        request for the bench gate ('departs within one step')."""
        if not self._deadlines_active:
            return
        now = self._clock()
        states = [*self.scheduler.waiting,
                  *list(self.scheduler.running.values())]
        for st in states:
            if st.deadline_at is None or now < st.deadline_at or st.done:
                continue
            self.stats["deadline_overrun_s"][st.request.uid] = (
                now - st.deadline_at)
            self._depart(st, Status.TIMED_OUT, "deadline")

    def _observe_health(self) -> None:
        """Feed the ladder one step of signals; apply DRAINING (waiting
        requests fail now so ``run()`` converges — residents finish)."""
        if self.health is None:
            return
        state = self.health.observe(
            step=self._tick,
            pressure=self.cache_mgr.utilization(),
            preemptions=self.scheduler.stats["preempted"],
            timeouts=self.scheduler.stats["timed_out"],
            step_fault=self._step_faulted)
        self._step_faulted = False
        self.stats["health"] = state.name
        self.stats["health_transitions"] = len(self.health.transitions)
        if state >= HealthState.DRAINING:
            for st in list(self.scheduler.waiting):
                self._depart(st, Status.FAILED, "draining")

    def _poison_slot(self, running) -> None:
        """The ``logits`` fault site: overwrite one RUNNING slot's arena
        region with NaN, so its next decode/verify logits go non-finite
        and the quarantine path departs it.  The victim pick is
        deterministic (injector ``choose``).  Slots serving as prefix
        donors — or hosting registered prefix pages a later fork could
        map — are excluded: the blast radius must stay one slot so the
        survivor-bit-identity contract is testable."""
        cands = sorted(running, key=lambda s: s.slot)
        if self.prefix_sharing:
            donors = {st.share_src for st in
                      self.scheduler.running.values()
                      if st.share_src is not None
                      and st.share_src != st.slot}
            cands = [st for st in cands
                     if st.slot not in donors
                     and not self.cache_mgr.hosts_registered(st.slot)]
        if not cands:
            return
        victim = cands[self._injector.choose("logits", len(cands))]
        if self._nan_one is None:
            # NaN-filled batch=1 cache template, spliced by the existing
            # donated insert — no new executables for the poison path
            self._nan_one = jax.tree.map(
                lambda leaf: (jnp.full_like(leaf, jnp.nan)
                              if jnp.issubdtype(leaf.dtype, jnp.inexact)
                              else leaf),
                self._one_cache)
        self._cache = self._insert(self._cache, self._nan_one,
                                   jnp.int32(victim.slot))
        self._poisoned_slots.add(victim.slot)
        self.stats["poisoned"] += 1
        self._step_faulted = True

    def _scrub_slot(self, slot: int) -> None:
        """Reset a poisoned slot's arena region to zeros before a new
        resident prefills into it.  Monolithic prefill re-splices the
        whole region anyway, but chunked prefill only writes chunk-sized
        slices — a stale NaN tail would then re-trigger quarantine for the
        innocent next resident through the masked value aggregation
        (softmax weight 0 times NaN is still NaN)."""
        if self._zero_one is None:
            self._zero_one = jax.tree.map(
                lambda leaf: jnp.zeros_like(leaf), self._one_cache)
        self._cache = self._insert(self._cache, self._zero_one,
                                   jnp.int32(slot))
        self._poisoned_slots.discard(slot)

    def _note_prefill_shape(self, key) -> None:
        self._prefill_shapes.add(key)
        self.stats["prefill_compiles"] = len(self._prefill_shapes)

    def _first_token(self, st: RequestState) -> None:
        if st.ttft_s is not None:
            return      # preemption recompute: keep the *first* first-token
        st.first_token_at = self._clock()
        st.ttft_s = st.first_token_at - st.submitted_at
        self.stats["ttft_s"][st.request.uid] = st.ttft_s

    # -- intake --------------------------------------------------------------
    def submit(self, request: Request) -> RequestState:
        # shedding / draining replicas refuse intake up front — the typed
        # rejection is the router's signal to try another replica
        if self._health_state >= HealthState.SHEDDING:
            raise AdmissionRejected(request.uid,
                                    self._health_state.name.lower())
        # prompt-vs-arena validation happens here in *both* prefill modes:
        # a monolithic prompt longer than the slot arena used to slip past
        # this method (the splice's dynamic_update_slice clamps = silently
        # shifts the write) and only get caught downstream by the
        # scheduler's prompt+generation bound.  Same structured error
        # either way.
        need = request.prompt.shape[0] + self.prefix_extra + 1
        if need > self.max_seq:
            raise ValueError(
                f"request {request.uid!r}: prompt needs {need} rows "
                f"but a slot holds max_seq={self.max_seq}")
        plan = None
        if self.prefill_chunks is not None:
            plan = chunking.chunk_plan(request.prompt.shape[0],
                                       self.prefill_chunks)
            if sum(plan) > self.max_seq:
                # the padded final chunk would run past the slot arena and
                # dynamic_update_slice clamps (= silently shifts the write);
                # reject before the scheduler enqueues anything
                raise ValueError(
                    f"request {request.uid!r}: padded chunk plan {plan} "
                    f"needs {sum(plan)} rows but a slot holds "
                    f"max_seq={self.max_seq}")
        if self.prefix_sharing:
            # advisory index consult: admission keeps its conservative
            # full-prompt reservation (the fork happens at first-chunk
            # ingestion, against whatever pages are live *then*), but the
            # hit statistic is visible to callers/benchmarks immediately
            if self.cache_mgr.lookup(
                    request.prompt, request.prompt.shape[0] - 1,
                    require_snapshot=self._needs_state_snapshot):
                self.stats["prefix_hits"] += 1
        st = self.scheduler.submit(request, chunk_plan=plan)
        st.submitted_at = self._clock()
        if request.deadline_ms is not None:
            st.deadline_at = st.submitted_at + request.deadline_ms / 1e3
            self._deadlines_active = True
        self.stats["requests"] += 1
        if not request.sampling.is_greedy:
            self.stats["sampled_requests"] += 1
        self._results[request.uid] = st
        return st

    # -- admission (prefill + splice) ----------------------------------------
    def _admit(self) -> None:
        with spans.span("serving.admit"):
            for st in self.scheduler.schedule(tick=self._tick):
                if st.admitted_at is None:
                    # first departure from WAITING; a preemption recompute
                    # keeps it, as it keeps ttft_s
                    st.admitted_at = self._clock()
                if st.slot is None:
                    # evicted again by an earlier admission's row
                    # reservation before we got to prefill it — it's back
                    # in the wait queue
                    continue
                if st.slot in self._poisoned_slots:
                    self._scrub_slot(st.slot)
                if st.status == Status.PREFILLING:
                    # chunked: park the slot's position pointer at the sentinel
                    # so in-flight decode steps cannot touch the slot — KV
                    # scatters for the row go out of bounds and are dropped,
                    # and recurrent-state writes (SSD state is not
                    # position-addressed) mask on pos < PARKED_POS inside the
                    # family's rows_scatter
                    self._pos = _park_slot_jit(self._pos, jnp.int32(st.slot),
                                               jnp.int32(PARKED_POS))
                    self._host_pos[st.slot] = PARKED_POS
                    continue
                if st.status != Status.RUNNING:
                    continue
                self._slot_gen[st.slot] += 1
                req = st.request
                extras = {k: jnp.asarray(v)[None] for k, v in
                          (req.extras or {}).items()}
                prompt = jnp.asarray(req.prompt)[None, :]
                logits, one_cache = self._prefill(prompt, self._one_cache,
                                                  extras)
                self.stats["prefills"] += 1
                self._note_prefill_shape(("prefill", int(prompt.shape[1])))
                self._cache = self._insert(self._cache, one_cache,
                                           jnp.int32(st.slot))
                if self.spec is not None:
                    # mirror the prompt into the draft arena (logits discarded)
                    # so both caches agree on rows [0, prompt_len) — the
                    # lockstep invariant every spec round relies on.  A
                    # preemption recompute re-runs both, so the caches can
                    # never drift apart.
                    _, draft_one = self._draft_prefill_fn(
                        self._draft_params, prompt, self._draft_one_cache, {})
                    self._draft_cache = self._insert(self._draft_cache,
                                                     draft_one,
                                                     jnp.int32(st.slot))
                self._activate_slot(st, logits)

    def _activate_slot(self, st: RequestState, logits) -> None:
        """Sample the prompt's first token off ``logits`` (1, V) and put
        the slot into the decode batch — shared by monolithic admission
        and the chunked path's final chunk.

        The first generated token occupies cache row ``pos0``, so it is
        drawn with the decode-path key at q = pos0: the draw is identical
        whether the prompt arrived monolithically or chunked (the final
        chunk's logits equal monolithic prefill's), and a preemption
        recompute replays it exactly.  The slot's sampling vectors are
        (re)written here, before the slot joins the decode batch."""
        slot = st.slot
        sp = st.request.sampling
        seed = sampling.resolve_seed(sp, self.base_seed)
        pos0 = st.prompt_len + self.prefix_extra
        # prefill-path quarantine: non-finite prompt logits (poisoned
        # arena rows, bad weights) fail the request before it can commit
        # a garbage first token.  The check syncs with the token0 read
        # below, so it adds no extra host round-trip.
        ok0 = jnp.isfinite(logits).all()
        if sp.is_greedy:    # temp <= 0 ⟺ argmax: skip the masked transform
            token0 = jnp.argmax(logits[0], -1).astype(jnp.int32)
        else:
            token0 = sampling.sample_first(logits, seed, pos0, sp)
        with spans.wait("first_token", self.stats):
            ok = bool(ok0)
        if not ok:
            self.stats["quarantined"] += 1
            self._step_faulted = True
            self._depart(st, Status.FAILED, "nan-logits")
            return
        self._samp = sampling.write_slot(self._samp, slot, sp, seed)
        if self.prefix_sharing:
            # (re)write the slot's share vectors before it joins the
            # decode batch: forks read their shared prefix rows from the
            # donor's region, everyone else gets the identity mapping
            src = st.share_src if st.share_src is not None else slot
            self._share = _set_share_jit(self._share, jnp.int32(slot),
                                         jnp.int32(src),
                                         jnp.int32(st.share_len))
        # reading token0 syncs the host on this prefill only; in-flight
        # decode steps keep running on the device
        with spans.wait("first_token", self.stats):
            tok = int(token0)
        self._first_token(st)
        self._activate(slot, tok, pos0)
        self.stats["tokens_out"] += 1
        # first token may finish the request immediately, or its row
        # reservation may evict a younger running sequence — deactivate
        # every departed slot in the decode batch
        for dslot, _ in self.scheduler.on_token(slot, tok):
            self._retire_slot(dslot)

    def _activate(self, slot: int, token: int, pos0: int) -> None:
        """Put ``slot`` into the decode batch at ``token`` / ``pos0``."""
        self._tokens, self._pos, self._active = _set_slot_jit(
            self._tokens, self._pos, self._active, jnp.int32(slot),
            jnp.int32(token), jnp.int32(pos0))
        self._host_pos[slot] = pos0
        self._host_active[slot] = 1

    def _retire_slot(self, slot: int) -> None:
        """Take ``slot`` out of the decode batch and park its position:
        an idle slot attends no arena rows, so flash-decode fetches none
        of it, and its garbage row write is dropped."""
        self._pos, self._active = _retire_slot_jit(
            self._pos, self._active, jnp.int32(slot), jnp.int32(PARKED_POS))
        self._host_pos[slot] = PARKED_POS
        self._host_active[slot] = 0

    def _prefill(self, prompt, one_cache, extras):
        # compile-cached per prompt length (bucket prompts upstream if
        # compile churn matters — or use prefill_chunks)
        return self._prefill_fn(self.params, prompt, one_cache, extras)

    # -- chunked prefill (stripmined prompt ingestion) ------------------------
    def _advance_prefill(self) -> None:
        """Ingest prompt chunks for PREFILLING slots, up to
        ``prefill_budget`` tokens this step (always at least one chunk, so
        prefill can never starve).

        Order is least-ingested-first (ties broken by arrival): a short
        prompt admitted next to a half-ingested long one takes the next
        chunk slot and reaches its first token within a couple of steps —
        TTFT stops depending on the longest co-resident prompt.  Every
        other step the FIFO-oldest PREFILLING slot is first handed one
        chunk ahead of that order, so a steady stream of fresh pos-0
        arrivals cannot starve a long prompt's ingestion."""
        if self.prefill_chunks is None:
            return
        with spans.span("serving.prefill"):
            self._prefill_tick += 1
            spent = 0
            budget = self._effective_prefill_budget()
            faulted: set = set()    # slots whose ingest dispatch was dropped
            #                         this step (chunk fault site): they stall
            #                         one full step, cursor unmoved

            def prefilling():
                return [st for st in self.scheduler.running.values()
                        if st.status == Status.PREFILLING
                        and st.slot is not None]

            if self._prefill_tick % 2:
                states = prefilling()
                if not states:
                    return
                oldest = min(states, key=lambda s: s.seq)
                # the oldest PREFILLING slot never defers (deferral waits on a
                # strictly older pure prefill), so this can only fork
                self._maybe_fork(oldest)
                size = oldest.chunk_plan[oldest.chunk_idx]
                if self._prefill_one_chunk(oldest, size):
                    spent += size
                else:
                    faulted.add(oldest.slot)
            while True:
                states = sorted(prefilling(),
                                key=lambda s: (s.prefill_pos, s.seq))
                if not states:
                    return
                progressed = False
                for st in states:
                    if st.status != Status.PREFILLING or st.slot is None:
                        continue        # departed via an earlier activation
                    if st.slot in faulted:
                        continue        # dropped dispatch: stalled this step
                    if self._maybe_fork(st):
                        continue        # deferred: an older donor is still
                        #                 publishing this slot's prefix
                    size = st.chunk_plan[st.chunk_idx]
                    # always ingest at least one chunk per step (progress
                    # guarantee), then stay within the budget
                    if spent and spent + size > budget:
                        return
                    if not self._prefill_one_chunk(st, size):
                        faulted.add(st.slot)
                        continue
                    spent += size
                    progressed = True
                if not progressed:
                    return              # everything left is deferred/faulted

    def _maybe_fork(self, st: RequestState) -> bool:
        """At a slot's first ingestion under prefix sharing: try to remap
        its leading pages onto a registered prefix chain (zero-ingestion
        CoW fork).  Returns True if the slot should *defer* this round —
        a strictly older pure prefill is still publishing a longer usable
        prefix of this prompt (it progresses every step, so the wait is
        bounded; if it departs, the deferral lapses)."""
        if (not self.prefix_sharing or st.prefill_pos or st.share_len
                or st.share_src is not None):
            return False
        mgr = self.cache_mgr
        ps = mgr.page_size
        plen = st.prompt_len
        prompt = st.request.prompt
        limit = plen - 1        # every fork ingests >= 1 real token
        m = mgr.lookup(prompt, limit,
                       require_snapshot=self._needs_state_snapshot)
        m = self._trim_match(m, plen)
        got = m.shared_len if m else 0
        best_pending = 0
        for other in self.scheduler.running.values():
            if (other is st or other.status != Status.PREFILLING
                    or other.slot is None or other.seq >= st.seq
                    or other.share_len or other.share_src is not None):
                continue
            p = _common_prefix_len(other.request.prompt, prompt)
            p = min(p, limit, other.prompt_len // ps * ps) // ps * ps
            best_pending = max(best_pending, p)
        if best_pending > got:
            self.stats["prefix_deferrals"] += 1
            return True
        if not m:
            return False
        # page accounting: the fork swaps its first k private pages for
        # the chain's k refcounted pages (freeing k to the pool) and may
        # need extra tail pages when the re-cut plan's padding lands
        # differently — make sure the pool covers that before committing
        rows = m.shared_len + sum(chunking.tail_plan(plen, m.shared_len,
                                                     self.prefill_chunks))
        k = len(m.entries)
        held = len(mgr.page_table(st.slot))
        new_len = max(rows, mgr.length(st.slot))
        extra = mgr.pages_for(new_len) - held
        if extra > mgr.free_pages + k:
            return False        # pool too tight to re-cut: ingest normally
        res = mgr.fork(st.slot, m)
        if not res:
            return False
        if extra > 0:
            mgr.extend(st.slot, new_len)
        if m.snapshot is not None:
            # recurrent families: resume the SSD recurrence from the
            # donor's checkpointed state at the divergence boundary
            self._cache = self._splice_state(self._cache,
                                             list(m.snapshot),
                                             jnp.int32(st.slot))
        st.share_src = res.src_slot
        st.share_len = res.shared_len
        st.chunk_plan = chunking.tail_plan(plen, res.shared_len,
                                           self.prefill_chunks)
        st.chunk_idx = 0
        st.prefill_pos = res.shared_len
        self.stats["forks"] += 1
        self.stats["shared_prompt_tokens"] += res.shared_len
        return False

    def _trim_match(self, m: Optional[PrefixMatch],
                    plen: int) -> Optional[PrefixMatch]:
        """Cut a prefix match back until the shared pages plus the re-cut
        tail plan fit the slot arena (tail padding can land past where the
        full-prompt plan's did).  Recurrent families additionally re-trim
        to a snapshot boundary."""
        if m is None:
            return None
        entries = list(m.entries)
        ps = self.cache_mgr.page_size
        while entries:
            sl = len(entries) * ps
            rows = sl + sum(chunking.tail_plan(plen, sl,
                                               self.prefill_chunks))
            if rows <= self.max_seq:
                break
            entries.pop()
            if self._needs_state_snapshot:
                while entries and entries[-1].snapshot is None:
                    entries.pop()
        if not entries:
            return None
        return PrefixMatch(entries=tuple(entries),
                           src_slot=m.src_slot,
                           shared_len=len(entries) * ps)

    def _register_prefix(self, st: RequestState) -> None:
        """Publish a pure slot's ingested prefix pages into the index so
        later arrivals can fork onto them.  Recurrent families checkpoint
        the slot's state at page-aligned chunk boundaries — the only
        points a fork can resume the recurrence from."""
        upto = min(st.prefill_pos, st.prompt_len)
        ps = self.cache_mgr.page_size
        snap = None
        if self._needs_state_snapshot and upto and upto % ps == 0:
            snap = self._extract_state(self._cache, jnp.int32(st.slot))
        self.cache_mgr.register_prefix(st.slot, st.request.prompt, upto,
                                       snapshot=snap)

    def _prefill_one_chunk(self, st: RequestState, size: int) -> bool:
        """Ingest one chunk; False if the dispatch was dropped by the
        ``chunk`` fault site (cursor unmoved — the slot retries next
        step, replaying the identical chunk)."""
        if self._injector is not None and self._injector.fire("chunk"):
            self._step_faulted = True
            return False
        req = st.request
        plen = st.prompt_len
        start = st.prefill_pos
        real = min(size, plen - start)
        with spans.span("serving.chunk", uid=req.uid, size=size,
                        valid=real):
            chunk = np.zeros((size,), np.int32)
            chunk[:real] = req.prompt[start:start + real]
            is_last = st.chunk_idx == len(st.chunk_plan) - 1
            # index of the chunk's last *real* token: size - 1 except on a
            # padded final chunk.  Recurrent families read it as the chunk's
            # valid length (pad positions are masked out of the SSD state
            # recurrence); the final chunk's logits are taken there.
            last_idx = real - 1
            if self.prefix_sharing:
                src = st.share_src if st.share_src is not None else st.slot
                logits, self._cache, *counts = self._chunk_fn(
                    self.params, self._cache, jnp.asarray(chunk)[None, :],
                    jnp.int32(st.slot), jnp.int32(start), jnp.int32(last_idx),
                    jnp.int32(src), jnp.int32(st.share_len))
            else:
                logits, self._cache, *counts = self._chunk_fn(
                    self.params, self._cache, jnp.asarray(chunk)[None, :],
                    jnp.int32(st.slot), jnp.int32(start), jnp.int32(last_idx))
            self._chunk_counts += counts
            if self.spec is not None:
                # lockstep draft ingestion: the identical chunk goes into
                # the draft arena (same slot, same rows; logits discarded),
                # so a slot finishing prefill has BOTH caches live on
                # [0, prompt_len)
                _, self._draft_cache = self._draft_chunk_fn(
                    self._draft_params, self._draft_cache,
                    jnp.asarray(chunk)[None, :], jnp.int32(st.slot),
                    jnp.int32(start), jnp.int32(last_idx))
            self.stats["prefill_chunks"] += 1
            self.stats["prefill_rows"] += size
            self._note_prefill_shape(("chunk", size))
            st.prefill_pos = start + size
            st.chunk_idx += 1
            if self.prefix_sharing and st.share_src is None:
                self._register_prefix(st)
            if not is_last:
                return True
            # final chunk: sample the first token and join the decode batch
            self.scheduler.finish_prefill(st.slot)
            # steps submitted mid-prefill are stale for this slot: drop them
            self._slot_gen[st.slot] += 1
            self._activate_slot(st, logits)
            return True

    # -- speculative rounds ---------------------------------------------------
    def _spec_round(self) -> None:
        """One draft-propose / chunk-verify / commit round over the RUNNING
        slots — the speculative replacement for a decode-step submission.

        Per round: (1) the draft runs k batched micro-steps over the whole
        slot batch, feeding each slot's current token then its own
        proposals, writing draft K/V at rows [pos, pos+k) and drawing
        proposal j+1 with the slot's (seed, pos+j+1) key; (2) the target
        verifies each slot with ONE chunk-shaped call over
        ``[current, d_1..d_{k-1}]`` at rows [pos, pos+k), whose logits rows
        are bit-identical to k sequential decode steps, and draws the
        Gumbel replay at all k positions inside the executable; (3) the
        host accepts the longest leading proposal run matching the target's
        draws and commits those tokens plus — on a rejection — the draw at
        the first mismatch (the resample).  Rollback is pure cursor
        arithmetic: rejected rows in both arenas are dead (never attended
        before the next round's chunk overwrites them), so the committed
        stream is the target's own stream verbatim — bit-identical to
        non-speculative decode for every (seed, temperature).

        The round is synchronous (its commits feed the next round's
        proposals), but all device work — k draft steps + per-slot
        verifies — is launched before the single host sync that reads the
        proposal and draw vectors together.
        """
        running = [st for st in self.scheduler.running.values()
                   if st.status == Status.RUNNING]
        if not running:
            return
        k = self.spec.k
        with spans.span("serving.spec_round", k=k):
            tok0 = np.zeros((self.max_slots,), np.int32)
            pos0 = np.full((self.max_slots,), PARKED_POS, np.int32)
            for st in running:
                # the slot's current (committed, not yet cached) token and the
                # arena row it will occupy; non-RUNNING slots park at the
                # sentinel so every draft scatter for them is dropped —
                # PREFILLING slots' freshly-ingested rows stay untouched
                tok0[st.slot] = st.generated[-1]
                pos0[st.slot] = (st.prompt_len + self.prefix_extra
                                 + len(st.generated) - 1)
            all_greedy = all(st.request.sampling.is_greedy for st in running)
            draft_fn = (self._draft_propose_greedy if all_greedy
                        else self._draft_propose)
            toks = jnp.asarray(tok0)
            base = jnp.asarray(pos0)
            proposals = []
            for j in range(k):
                toks, self._draft_cache = draft_fn(
                    self._draft_params, toks, self._draft_cache, base + j,
                    self._samp)
                proposals.append(toks)
            self.stats["spec_draft_steps"] += k
            # one host sync for the round's proposals (they shape the verify
            # chunks); the per-slot verify calls then launch back-to-back and
            # their draw vectors are read after all are in flight
            with spans.wait("spec_readback", self.stats):
                props = np.stack([np.asarray(p) for p in proposals])  # (k, B)
            if self._injector is not None and self._injector.fire("draft"):
                # corrupt the round's proposals host-side.  Self-correcting by
                # construction: acceptance compares against the target's own
                # draws, so the committed stream is unchanged — only the
                # acceptance rate collapses for this round.
                props = (props + 1) % self.cfg.vocab
                self._step_faulted = True
            reads = []
            for st in running:
                slot = st.slot
                chunk = np.concatenate(
                    [[tok0[slot]], props[:k - 1, slot]]).astype(np.int32)
                vfn = (self._verify_greedy if st.request.sampling.is_greedy
                       else self._verify)
                draws, okv, self._cache = vfn(
                    self.params, self._cache, jnp.asarray(chunk)[None, :],
                    jnp.int32(slot), jnp.int32(pos0[slot]), self._samp)
                reads.append((st, slot, draws, okv))
            self._verify_shapes.add(k)
            self.stats["spec_verify_calls"] += len(reads)
            self.stats["spec_verify_compiles"] = len(self._verify_shapes)
            outcomes = []
            for st, slot, draws, okv in reads:
                if st.status != Status.RUNNING or st.slot != slot:
                    continue    # preempted by an earlier commit this round:
                    #             its generated stream was rewound, recompute
                    #             replays it — this round's draws are void
                with spans.wait("spec_readback", self.stats):
                    draws = np.asarray(draws)
                    ok = bool(np.asarray(okv))
                if not ok:
                    # verify logits went non-finite: quarantine the slot, no
                    # token of this round commits (survivors are untouched —
                    # the NaN lives in the victim's own arena region)
                    self.stats["quarantined"] += 1
                    self._step_faulted = True
                    self._depart(st, Status.FAILED, "nan-logits")
                    continue
                a, committed = sampling.accept_tokens(props[:, slot], draws)
                n, _ = self.scheduler.on_tokens(slot, committed)
                self.stats["tokens_out"] += n
                outcomes.append((st.request.uid, a, k))
            self.spec.observe_round(outcomes)
            self.stats["spec_rounds"] += 1
            self.stats["decode_steps"] += 1
            if not all_greedy:
                self.stats["sampled_steps"] += 1

    # -- the continuous-batching loop ----------------------------------------
    def step(self) -> None:
        """One engine iteration: retire lagged outputs, expire deadlines,
        observe health, admit, ingest prompt chunks, decode — or, under
        ``EngineConfig.speculative`` (and a healthy-enough ladder), run one
        synchronous draft-propose/verify/commit round instead of submitting
        a decode step."""
        self._tick += 1
        self.stats["steps"] += 1
        with spans.span("serving.step", tick=self._tick):
            self._drain_pending(limit=self.depth)
            self._expire_deadlines()
            self._observe_health()
            self._admit()
            self.stats["slot_steps_prefilling"] += sum(
                st.status == Status.PREFILLING
                for st in self.scheduler.running.values())
            self._advance_prefill()
            running = [st for st in self.scheduler.running.values()
                       if st.status == Status.RUNNING]
            if not running:
                return
            inj = self._injector
            if inj is not None and inj.fire("decode"):
                # dropped dispatch: the whole decode step / spec round stalls
                # one engine step.  Positions don't advance, so no slot's
                # stream can diverge — the fault costs latency, never tokens.
                self._step_faulted = True
                return
            if inj is not None and inj.fire("logits"):
                self._poison_slot(running)
            if self.spec is not None \
                    and self._health_state < HealthState.DEGRADED:
                if self._pending:
                    # mode transition (queue decode -> spec rounds, i.e. the
                    # ladder just recovered): retire every in-flight queue
                    # step first so a committed token can't be re-credited
                    self._queue.drain()
                    self._drain_pending(limit=0)
                self._spec_round()
                self._spec_resync = True
                return
            if self._spec_resync:
                # mode transition (spec rounds -> queue decode, the ladder
                # degraded): the device slot vectors lag the spec commits —
                # resync tokens/pos from host state for every RUNNING slot
                for st in running:
                    self._activate(st.slot, st.generated[-1],
                                   st.prompt_len + self.prefix_extra
                                   + len(st.generated) - 1)
                self._spec_resync = False
            # executable choice: only a step with a sampled RUNNING slot
            # pays the sampling transform; pure-greedy steps run the argmax
            # twin
            self._use_sampling = any(not st.request.sampling.is_greedy
                                     for st in running)
            state = (self._tokens, self._cache, self._pos, self._active,
                     self._samp)
            if self.prefix_sharing:
                state = state + (self._share,)
            # strips flash-decode fetches per layer, from the host's view
            # of the positions this step decodes at (no device readback)
            live = np.where(self._host_pos < self.max_seq, self._host_pos, 0)
            kv_strips, grid_strips = strip_counts(live, self.max_seq)
            with spans.span("serving.decode", slots=len(running),
                            kv_strips=kv_strips, grid_strips=grid_strips):
                out = self._queue.submit(state)
            self._host_pos += self._host_active
            self.stats["decode_kv_strips"] += kv_strips
            # rebind to the outputs: the submitted buffers were donated and
            # are dead from here on
            out, counts = ((out[:-1], out[-1:]) if self._counters
                           else (out, ()))
            if self.prefix_sharing:
                (self._tokens, self._cache, self._pos, self._active,
                 self._samp, self._share, read, okv) = out
            else:
                (self._tokens, self._cache, self._pos, self._active,
                 self._samp, read, okv) = out
            self.stats["decode_steps"] += 1
            snapshot = {slot: (st, self._slot_gen[slot])
                        for slot, st in self.scheduler.running.items()}
            self._pending.append((read, okv, counts, self._chunk_counts,
                                  snapshot))
            self._chunk_counts = []

    def _add_counts(self, counts, prefixes) -> None:
        """Add read-back step counters to ``stats`` under each prefix."""
        for vec in counts:
            for name, v in zip(self._counters, np.asarray(vec).tolist()):
                for prefix in prefixes:
                    self.stats[prefix + name] += v

    def _drain_pending(self, *, limit: int) -> None:
        """Process token outputs older than ``limit`` steps (blocking only
        on steps the queue has already forced to completion)."""
        if limit == 0 and self._chunk_counts:
            # the final drain: chunks no decode step followed yet
            self._add_counts(self._chunk_counts, ("",))
            self._chunk_counts = []
        if len(self._pending) <= limit:
            return
        with spans.span("serving.retire"):
            while len(self._pending) > limit:
                tokens, okv, counts, chunk_counts, snapshot = \
                    self._pending.popleft()
                with spans.wait("readback", self.stats):
                    host_tokens = np.asarray(tokens)
                    host_ok = np.asarray(okv)
                    self._add_counts(counts, ("", "decode_"))
                    self._add_counts(chunk_counts, ("",))
                for slot, (st, gen) in snapshot.items():
                    # stale entries: the request left this slot (finished
                    # or preempted) after the step was submitted, was still
                    # prefilling when it was submitted (gen bumped on
                    # activation), or the slot was recycled to a newer
                    # admission
                    if (st.status != Status.RUNNING or st.slot != slot
                            or gen != self._slot_gen[slot]):
                        continue
                    if not host_ok[slot]:
                        # slot quarantine: non-finite logits.  The first
                        # poisoned entry departs the slot FAILED before any
                        # poisoned token commits (FIFO drain), and the later
                        # in-flight entries for it die on the status guard
                        # above.  Co-resident slots are untouched: the NaN
                        # lives in the victim's own arena region, and the
                        # flash kernels mask dead rows with a select, so it
                        # cannot leak into another slot's softmax.
                        self.stats["quarantined"] += 1
                        self._step_faulted = True
                        self._depart(st, Status.FAILED, "nan-logits")
                        continue
                    self.stats["tokens_out"] += 1
                    deps = self.scheduler.on_token(slot,
                                                   int(host_tokens[slot]))
                    for dslot, _ in deps:
                        self._retire_slot(dslot)

    def evacuate(self) -> list:
        """Remove every non-terminal request from service for migration and
        return their immutable :class:`Request` objects in arrival order.

        The drain-with-migration half of the router's ``drain()``: because
        every stream is a pure function of (seed, absolute position) — the
        same contract preemption recompute relies on — resubmitting the
        returned requests to *any* sibling replica replays their token
        streams bit-identically from the prompt.  Evacuated requests depart
        ``MIGRATED`` (counted separately from failures), their slots leave
        the decode batch, their pages free through the normal refcount
        path, and their results are dropped here — ownership moves to
        wherever the router re-places them."""
        states = [*self.scheduler.waiting,
                  *list(self.scheduler.running.values())]
        states.sort(key=lambda s: s.seq)
        moved = []
        for st in states:
            if st.done:
                continue
            self._depart(st, Status.MIGRATED, "migrated")
            self._results.pop(st.request.uid, None)
            moved.append(st.request)
        return moved

    def run(self, *, max_steps: Optional[int] = None) -> dict:
        """Drive until every submitted request finishes.  Returns
        {uid: (gen_tokens,) np.int32}."""
        steps = 0
        while not self.scheduler.all_done:
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"engine did not converge in {max_steps} steps "
                    f"(waiting={len(self.scheduler.waiting)}, "
                    f"running={len(self.scheduler.running)})")
            self.step()
            steps += 1
            # nothing in flight and nothing running: force lagged retire
            if not self.scheduler.running and self._pending:
                self._queue.drain()
                self._drain_pending(limit=0)
        self._queue.drain()
        self._drain_pending(limit=0)
        return {uid: st.output() for uid, st in self._results.items()}
