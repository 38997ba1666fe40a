"""Zero-copy KV arena: donation safety + in-place lowering claims.

The serving hot path's contract after the arena rewrite: (a) every jitted
mutation of the resident KV arena donates it, and the backend actually
reuses the buffer (pointer identity where the platform supports donation);
(b) the compiled chunk step's copied bytes are bounded by the *chunk's*
rows, independent of arena width (the cost-analysis claim check); (c) the
compiled decode step lowers its cache update as in-place dynamic-update-
slices/scatters, not arena-sized copies; (d) the engine's compiled-step
cache is weakly keyed, so retired models release their executables.
"""
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core import hlo_analysis
from repro.models import registry
from repro.models.layers import PARKED_POS
from repro.runtime.serving import Request, SamplingParams, ServingEngine
from repro.runtime.serving import sampling
from repro.runtime.serving.engine import (_compiled_decode,
                                          _compiled_prefill_chunk,
                                          _insert_jit)

from conftest import family_cfgs

TINY = ArchConfig(name="tiny-zc", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab=97, head_dim=8,
                  param_dtype="float32", act_dtype="float32", max_seq=64)

# non-dense family configs + the ``family_model`` fixture are shared with
# test_chunked_prefill via conftest.py (one pinned regime)

SLOTS, SEQ, CHUNK = 3, 48, 8


@pytest.fixture(scope="module")
def tiny_model():
    model = registry.build_model(TINY)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    return model, params


def _leaf_ptrs(tree):
    return [leaf.unsafe_buffer_pointer() for leaf in jax.tree.leaves(tree)]


def _require_donation(donated_input):
    """Skip (rather than fail) on platforms where donation is a no-op —
    e.g. interpret-mode CI shims or backends without buffer donation."""
    if not any(leaf.is_deleted() for leaf in jax.tree.leaves(donated_input)):
        pytest.skip("backend does not implement buffer donation")


# ---------------------------------------------------------------------------
# buffer reuse (pointer identity under donation)
# ---------------------------------------------------------------------------

def test_decode_step_reuses_donated_arena_buffer(tiny_model):
    model, params = tiny_model
    step = _compiled_decode(model, True)
    cache = model.init_cache(SLOTS, SEQ)
    tokens = jnp.zeros((SLOTS,), jnp.int32)
    pos = jnp.full((SLOTS,), 4, jnp.int32)
    active = jnp.ones((SLOTS,), jnp.int32)
    samp = sampling.init_slot_state(SLOTS)
    ptrs = _leaf_ptrs(cache)
    tokens, new_cache, pos, active, samp, read, ok = step(
        params, tokens, cache, pos, active, samp)
    _require_donation(cache)
    assert _leaf_ptrs(new_cache) == ptrs, \
        "decode step re-materialised the arena instead of reusing it"
    # the readback copy must be a *distinct* buffer: it outlives the token
    # state, which is donated into the next step
    assert read.unsafe_buffer_pointer() != tokens.unsafe_buffer_pointer()
    # second step: the arena stays resident in the same buffer
    tokens2, cache2, pos2, active2, samp2, read2, ok2 = step(
        params, tokens, new_cache, pos, active, samp)
    assert _leaf_ptrs(cache2) == ptrs
    # and the first step's readback is still host-readable
    np.asarray(read)


def test_chunk_step_reuses_donated_arena_buffer(tiny_model):
    model, params = tiny_model
    chunk_fn = _compiled_prefill_chunk(model, True)
    cache = model.init_cache(SLOTS, SEQ)
    toks = jnp.zeros((1, CHUNK), jnp.int32)
    ptrs = _leaf_ptrs(cache)
    logits, new_cache = chunk_fn(params, cache, toks, jnp.int32(1),
                                 jnp.int32(0), jnp.int32(CHUNK - 1))
    _require_donation(cache)
    assert _leaf_ptrs(new_cache) == ptrs, \
        "chunk step re-materialised the arena instead of reusing it"


def test_insert_splice_reuses_donated_arena_buffer(tiny_model):
    model, params = tiny_model
    cache = model.init_cache(SLOTS, SEQ)
    one = model.init_cache(1, SEQ)
    ptrs = _leaf_ptrs(cache)
    one_ptrs = _leaf_ptrs(one)
    new_cache = _insert_jit(cache, one, jnp.int32(2))
    _require_donation(cache)
    assert _leaf_ptrs(new_cache) == ptrs
    # the batch=1 prefill template is NOT donated (it is reused verbatim
    # by every monolithic admission)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(one))
    assert _leaf_ptrs(one) == one_ptrs


def test_engine_arena_is_single_resident_buffer(tiny_model):
    """Across an entire engine run — admissions, chunk ingestion, decode
    steps — the KV arena must live in one device buffer."""
    model, params = tiny_model
    rng = np.random.default_rng(0)
    eng = ServingEngine(model, TINY, params, max_slots=2, max_seq=64,
                        depth=2, prefill_chunks=(4, 8), donate=True)
    ptrs0 = _leaf_ptrs(eng._cache)
    for i, n in enumerate((5, 11, 7)):
        eng.submit(Request(uid=i, prompt=rng.integers(0, TINY.vocab, n)
                           .astype(np.int32), max_new_tokens=6))
    eng.run(max_steps=500)
    if not ptrs0:       # defensive; dense cache always has leaves
        pytest.skip("no cache leaves")
    try:
        ptrs1 = _leaf_ptrs(eng._cache)
    except Exception:
        pytest.skip("backend does not expose buffer pointers")
    if ptrs0 != ptrs1:
        # tolerated only where donation is unimplemented (no deletion ever
        # happened); on donating backends the arena must not move
        probe = jax.jit(lambda x: x + 1, donate_argnums=0)
        x = jnp.zeros((4,))
        probe(x)
        assert not x.is_deleted(), \
            "donating backend moved the resident arena"


def test_family_chunk_and_decode_reuse_donated_arena_buffer(family_model):
    """The rows/arena contract beyond dense: MoE/SSM/hybrid chunk
    ingestion and decode steps donate the arena and the backend reuses
    the buffers in place."""
    cfg, model, params = family_model
    step = _compiled_decode(model, True)
    chunk_fn = _compiled_prefill_chunk(model, True)
    cache = model.init_cache(SLOTS, SEQ)
    ptrs = _leaf_ptrs(cache)
    toks = jnp.zeros((1, CHUNK), jnp.int32)
    # a counting family (MoE) returns its step counters third
    logits, cache2, *counts = chunk_fn(params, cache, toks, jnp.int32(1),
                                       jnp.int32(0), jnp.int32(CHUNK - 1))
    _require_donation(cache)
    assert _leaf_ptrs(cache2) == ptrs, \
        f"{cfg.family}: chunk step re-materialised the arena"
    tokens = jnp.zeros((SLOTS,), jnp.int32)
    pos = jnp.full((SLOTS,), 4, jnp.int32)
    active = jnp.ones((SLOTS,), jnp.int32)
    samp = sampling.init_slot_state(SLOTS)
    out = step(params, tokens, cache2, pos, active, samp)
    assert _leaf_ptrs(out[1]) == ptrs, \
        f"{cfg.family}: decode step re-materialised the arena"


# ---------------------------------------------------------------------------
# parked-slot safety: sentinel indices must never alias live rows/state
# ---------------------------------------------------------------------------

def _family_cases():
    return [("dense", TINY)] + sorted(family_cfgs().items())


@pytest.mark.parametrize("family,cfg", _family_cases())
def test_prefill_chunk_parked_slot_cannot_alias_live_rows(family, cfg):
    """Regression: ``_slot_view``/the chunk scatter used to rely on
    ``dynamic_slice``/``dynamic_update_slice`` OOB *clamping* for an
    out-of-range slot index — a slot parked at the ``max_slots`` sentinel
    would clamp onto slot ``max_slots - 1`` and overwrite the last live
    slot's rows (or SSD state).  The slot view now clamps explicitly and
    every chunk write is a drop-on-OOB scatter, so a parked slot's chunk
    call must leave the entire arena bit-identical."""
    model = registry.build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    cache = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        model.init_cache(SLOTS, SEQ))
    before = jax.tree.map(np.asarray, cache)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, CHUNK)), jnp.int32)
    chunk_fn = jax.jit(model.prefill_chunk)
    for bad_slot in (SLOTS, SLOTS + 3):
        _, cache_out = chunk_fn(params, toks, cache, jnp.int32(bad_slot),
                                jnp.int32(0), jnp.int32(CHUNK - 1))
        jax.tree.map(
            lambda b, a: np.testing.assert_array_equal(np.asarray(a), b),
            before, cache_out)


def test_parked_slot_decode_preserves_recurrent_state(family_model):
    """A slot mid-chunked-prefill parks its position at PARKED_POS; the
    decode step must leave that slot's arena state bit-identical (KV
    scatters drop out of bounds; SSD state writes keep-mask on pos) while
    still updating the live slots."""
    cfg, model, params = family_model
    rng = np.random.default_rng(11)
    cache = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        model.init_cache(SLOTS, SEQ))
    before = jax.tree.map(np.asarray, cache)
    parked = 1
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, SLOTS), jnp.int32)
    pos = jnp.asarray([4, PARKED_POS, 5][:SLOTS], jnp.int32)
    _, cache_out = jax.jit(model.decode_step)(params, tokens, cache, pos)
    after = jax.tree.map(np.asarray, cache_out)

    def check_leaf(b, a):
        f = b.shape[1] // SLOTS
        sl = slice(parked * f, (parked + 1) * f)
        np.testing.assert_array_equal(a[:, sl], b[:, sl])
        # and the step was not a global no-op: some live slot's state moved
        return np.array_equal(a, b)

    unchanged = jax.tree.leaves(jax.tree.map(check_leaf, before, after))
    assert not all(unchanged), "decode step wrote nothing at all"


# ---------------------------------------------------------------------------
# cost-analysis claim checks (in-place lowering, chunk-row bounds)
# ---------------------------------------------------------------------------

_copied_bytes = hlo_analysis.copied_bytes


def _chunk_cost(model, params, slots):
    cache = model.init_cache(slots, SEQ)
    toks = jnp.zeros((1, CHUNK), jnp.int32)
    comp = jax.jit(
        lambda p, c, t, s, st, li: model.prefill_chunk(p, t, c, s, st, li),
        donate_argnums=1,
    ).lower(params, cache, toks, jnp.int32(0), jnp.int32(8),
            jnp.int32(0)).compile()
    arena_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(cache))
    return hlo_analysis.analyze(comp.as_text()), arena_bytes


def test_chunk_copied_bytes_bounded_by_chunk_rows(tiny_model):
    """The per-chunk write traffic must be O(chunk rows): the old
    extract/insert round-trip was O(slot) per chunk and the undonated
    splice O(arena)."""
    model, params = tiny_model
    cost, arena_bytes = _chunk_cost(model, params, SLOTS)
    row_bytes = (2 * TINY.n_layers * CHUNK * TINY.n_kv_heads
                 * TINY.hd * 4)                    # k+v chunk rows, f32
    copied = _copied_bytes(cost)
    # 2x for the cost model's read+write charge, 2x headroom for small
    # fused copies (logits, positions); far below one slot's rows
    assert copied <= 4 * row_bytes + 4096, (copied, row_bytes)
    slot_bytes = arena_bytes / SLOTS
    assert copied < slot_bytes, (copied, slot_bytes)


def test_chunk_bytes_independent_of_arena_width(tiny_model):
    """Doubling the number of slots must not change the chunk step's
    copied bytes (and must leave total bytes within noise): the zero-copy
    claim 'bytes move with the chunk, not the arena'."""
    model, params = tiny_model
    cost1, _ = _chunk_cost(model, params, SLOTS)
    cost2, _ = _chunk_cost(model, params, 2 * SLOTS)
    assert _copied_bytes(cost2) == pytest.approx(_copied_bytes(cost1)), \
        "chunk copied bytes scale with arena width"
    assert cost2.bytes <= cost1.bytes * 1.05, (cost2.bytes, cost1.bytes)


def test_decode_step_lowers_inplace_not_copies(tiny_model):
    """The donated decode step must alias the arena input to its output
    (memory_analysis) and spend copy bytes far below the arena size (the
    HLO cost model) — i.e. the cache update is an in-place scatter of the
    new rows, not an arena re-materialisation."""
    model, params = tiny_model
    cache = model.init_cache(SLOTS, SEQ)
    arena_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(cache))
    tokens = jnp.zeros((SLOTS,), jnp.int32)
    pos = jnp.full((SLOTS,), 4, jnp.int32)
    active = jnp.ones((SLOTS,), jnp.int32)

    def step(params, tokens, cache, pos, active):
        logits, cache = model.decode_step(params, tokens, cache, pos)
        return jnp.argmax(logits, -1), cache

    comp = jax.jit(step, donate_argnums=2).lower(
        params, tokens, cache, pos, active).compile()
    try:
        ma = comp.memory_analysis()
    except Exception:
        ma = None
    if ma is not None and ma.alias_size_in_bytes:
        assert ma.alias_size_in_bytes >= arena_bytes
    cost = hlo_analysis.analyze(comp.as_text())
    assert _copied_bytes(cost) < 0.5 * arena_bytes, \
        (dict(cost.bytes_by_op), arena_bytes)


def test_family_chunk_bytes_independent_of_arena_width(family_model):
    """Per family: doubling the slot count must not change a chunk step's
    copied bytes — K/V writes move with the chunk's rows, recurrent-state
    writes with one slot's carry, never with the arena."""
    cfg, model, params = family_model

    def cost(slots):
        cache = model.init_cache(slots, SEQ)
        toks = jnp.zeros((1, CHUNK), jnp.int32)
        comp = jax.jit(
            lambda p, c, t, s, st, li:
                model.prefill_chunk(p, t, c, s, st, li),
            donate_argnums=1,
        ).lower(params, cache, toks, jnp.int32(0), jnp.int32(8),
                jnp.int32(CHUNK - 1)).compile()
        return hlo_analysis.analyze(comp.as_text())

    c1, c2 = cost(SLOTS), cost(2 * SLOTS)
    assert _copied_bytes(c2) == pytest.approx(_copied_bytes(c1)), \
        f"{cfg.family}: chunk copied bytes scale with arena width"
    assert c2.bytes <= c1.bytes * 1.05, (c2.bytes, c1.bytes)


# ---------------------------------------------------------------------------
# engine-level: donation + preemption/recompute stay token-identical
# ---------------------------------------------------------------------------

def test_preemption_recompute_token_identical_with_donation(tiny_model):
    """Mid-run preemption discards a slot whose arena rows were written
    in place; deterministic recompute must replay identical tokens even
    though the donated arena was mutated under the preempted request."""
    model, params = tiny_model
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, TINY.vocab, n).astype(np.int32)
               for n in (9, 13, 10)]

    def reference(prompt, gen):
        cache = model.init_cache(1, 64)
        logits, cache = jax.jit(model.prefill)(
            params, jnp.asarray(prompt)[None], cache)
        toks = [int(jnp.argmax(logits[0]))]
        pos = jnp.asarray([len(prompt)], jnp.int32)
        tok = jnp.asarray([toks[0]], jnp.int32)
        step = jax.jit(model.decode_step)
        for _ in range(gen - 1):
            logits, cache = step(params, tok, cache, pos)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            toks.append(int(tok[0]))
            pos = pos + 1
        return np.array(toks, np.int32)

    want = [reference(p, 12) for p in prompts]
    eng = ServingEngine(model, TINY, params, max_slots=3, max_seq=64,
                        depth=2, page_size=4, num_pages=9,
                        prefill_chunks=(4, 8), donate=True)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=12))
    out = eng.run(max_steps=2000)
    assert eng.scheduler.stats["preempted"] > 0
    for i in range(3):
        np.testing.assert_array_equal(out[i], want[i])


def test_preemption_recompute_token_identical_sampled(tiny_model):
    """The stochastic extension of the preemption harness: a *sampled*
    request evicted mid-decode must replay a token-identical continuation
    on recompute, with the arena donated throughout.  Works because the
    draw at each position folds only (seed, position) — there is no RNG
    cursor to rewind, and no key material in the donated state.  The
    reference is the same workload in an unpressured pool (no preemption),
    so the comparison also pins batch-trajectory invariance."""
    model, params = tiny_model
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, TINY.vocab, n).astype(np.int32)
               for n in (9, 13, 10)]
    sps = [SamplingParams(temperature=0.9, top_k=25, top_p=0.92,
                          seed=300 + i) for i in range(3)]

    def run(num_pages):
        eng = ServingEngine(model, TINY, params, max_slots=3, max_seq=64,
                            depth=2, page_size=4, num_pages=num_pages,
                            prefill_chunks=(4, 8), donate=True)
        for i, (p, sp) in enumerate(zip(prompts, sps)):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=12,
                               sampling=sp))
        return eng.run(max_steps=2000), eng

    want, calm = run(num_pages=None)          # full arena: no pressure
    assert calm.scheduler.stats["preempted"] == 0
    out, pressured = run(num_pages=9)         # undersized: evictions
    assert pressured.scheduler.stats["preempted"] > 0
    for i in range(3):
        np.testing.assert_array_equal(out[i], want[i])


def test_family_preemption_recompute_token_identical_sampled(family_model):
    """The stochastic preemption harness beyond dense (the PR-4 dense
    harness, re-run per family on the ported rows/arena contract): a
    *sampled* MoE/SSM/hybrid request evicted mid-run — possibly
    mid-prefill, discarding chunk-threaded recurrent state — must replay
    a token-identical continuation on recompute, with the arena donated
    throughout.  The reference run is the same workload in an unpressured
    pool, so the comparison also pins batch-trajectory invariance (exact
    for SSM/hybrid; for MoE because its serving layer is dropless)."""
    cfg, model, params = family_model
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (9, 13, 10)]
    sps = [SamplingParams(temperature=0.9, top_k=25, top_p=0.92,
                          seed=300 + i) for i in range(3)]

    def run(num_pages):
        eng = ServingEngine(model, cfg, params, max_slots=3, max_seq=64,
                            depth=2, page_size=4, num_pages=num_pages,
                            prefill_chunks=(4, 8), donate=True)
        for i, (p, sp) in enumerate(zip(prompts, sps)):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=12,
                               sampling=sp))
        return eng.run(max_steps=2000), eng

    want, calm = run(num_pages=None)          # full arena: no pressure
    assert calm.scheduler.stats["preempted"] == 0
    out, pressured = run(num_pages=9)         # undersized: evictions
    assert pressured.scheduler.stats["preempted"] > 0
    for i in range(3):
        np.testing.assert_array_equal(out[i], want[i])


# ---------------------------------------------------------------------------
# weakly-keyed compiled-step cache
# ---------------------------------------------------------------------------

def test_compiled_step_cache_is_weak():
    """The per-model jit caches must hit for a live model and release the
    entry when the model is garbage-collected (lru_cache pinned every
    model — and its XLA executables — forever)."""
    model = registry.build_model(TINY)
    fn1 = _compiled_decode(model)
    fn2 = _compiled_decode(model)
    assert fn1 is fn2                      # same model -> cache hit
    assert id(model) in _compiled_decode.cache
    ref = weakref.ref(model)
    mid = id(model)
    del model, fn1, fn2
    gc.collect()
    assert ref() is None, "compiled-step cache kept the model alive"
    assert mid not in _compiled_decode.cache
