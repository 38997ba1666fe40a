"""Stripmined (chunked, length-bucketed) prefill: chunk planner, the
chunk-append attention kernel vs a naive oracle, model-level equivalence
with monolithic prefill, engine token-equality with sequential generation,
mid-prefill preemption rewind, and the prefill-compile/TTFT stats."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import registry
from repro.runtime.serving import (PagedKVCacheManager, Request,
                                   ServingEngine, Scheduler, Status,
                                   cache_insert, chunk_plan, padded_len)
from repro.runtime.serving.chunking import tail_plan

# ---------------------------------------------------------------------------
# chunk planner (pure host arithmetic)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("plen", [1, 7, 8, 9, 31, 32, 33, 100, 2048, 2049])
def test_chunk_plan_covers_with_bounded_padding(plen):
    buckets = (8, 16, 32)
    plan = chunk_plan(plen, buckets)
    assert all(c in buckets for c in plan)
    assert sum(plan) >= plen
    assert sum(plan) - plen < min(buckets)          # pad < smallest bucket
    assert padded_len(plen, buckets) == sum(plan)


def test_chunk_plan_is_greedy_largest_first_and_deterministic():
    assert chunk_plan(100, (8, 16, 32)) == [32, 32, 32, 8]
    assert chunk_plan(50, (8, 16, 32)) == [32, 16, 8]   # 48 real + pad 6
    assert chunk_plan(3, (8, 16, 32)) == [8]
    assert chunk_plan(100, (8, 16, 32)) == chunk_plan(100, (32, 16, 8))


def test_chunk_plan_rejects_bad_input():
    with pytest.raises(ValueError):
        chunk_plan(0, (8,))
    with pytest.raises(ValueError):
        chunk_plan(8, ())


@pytest.mark.parametrize("plen", [8, 16, 24, 32, 40, 48, 56, 64])
def test_chunk_plan_boundary_lengths_have_no_allpad_chunk(plen):
    """A prompt landing exactly on a bucket cover must not emit a
    zero-length (all-pad) trailing chunk: each chunk costs a compile-cache
    entry + a scheduler step, so every chunk must ingest >= 1 real token.
    (The off-by-one regression guard: ``rem >= b`` consumes an exactly-
    fitting bucket instead of falling through to the pad branch.)"""
    buckets = (8, 16, 32)
    plan = chunk_plan(plen, buckets)
    assert all(c > 0 for c in plan)
    # the final chunk holds at least one real token — never pure padding
    assert sum(plan[:-1]) < plen <= sum(plan)
    if plen % min(buckets) == 0:            # exact cover: zero padding
        assert sum(plan) == plen


def test_tail_plan_empty_tail_raises():
    """share_len == prompt_len would mean a fork ingests nothing and has
    no row to produce its first logits from — the planner must refuse,
    matching the engine's fork cap (lookup limit = prompt_len - 1)."""
    with pytest.raises(ValueError):
        tail_plan(32, 32, (8, 16, 32))
    with pytest.raises(ValueError):
        tail_plan(32, 33, (8, 16, 32))          # past the prompt
    with pytest.raises(ValueError):
        tail_plan(32, -1, (8, 16, 32))
    # share_len == 0 degenerates to the full-prompt plan, not an error
    assert tail_plan(32, 0, (8, 16, 32)) == chunk_plan(32, (8, 16, 32))


@pytest.mark.parametrize("share", [1, 3, 5, 7, 9, 15, 17, 31])
def test_tail_plan_page_unaligned_share_len(share):
    """The planner is pure arithmetic over ``prompt_len - shared_len`` —
    it accepts page-unaligned share lengths (alignment is the *cache
    manager's* contract, enforced at lookup: matches cover whole pages)
    and still satisfies the chunk_plan invariants on the tail."""
    buckets = (8, 16, 32)
    plen = 33
    plan = tail_plan(plen, share, buckets)
    tail = plen - share
    assert all(c in buckets for c in plan)
    assert sum(plan) >= tail
    assert sum(plan) - tail < min(buckets)      # pad < smallest bucket
    assert sum(plan[:-1]) < tail                # no all-pad trailing chunk


@pytest.mark.parametrize("tail", [1, 2, 7])
def test_tail_plan_tail_shorter_than_smallest_bucket(tail):
    """A fork diverging just before the prompt's end leaves a sub-bucket
    tail: one smallest-bucket chunk, mostly padding — never zero chunks,
    never a bucket the set doesn't contain."""
    buckets = (8, 16, 32)
    plen = 64
    plan = tail_plan(plen, plen - tail, buckets)
    assert plan == [min(buckets)]
    # and the engine-facing row bound holds: shared rows + padded tail
    rows = (plen - tail) + sum(plan)
    assert rows - plen < min(buckets)


def test_chunk_plan_boundary_engine_runs_one_chunk_per_bucket(tiny_model):
    """Engine-level boundary case: a prompt exactly equal to a bucket is
    ingested in exactly one chunk (no wasted all-pad step)."""
    model, params = tiny_model
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, TINY.vocab, 8).astype(np.int32)   # == bucket
    want = _reference(model, params, prompt, 4)
    eng = ServingEngine(model, TINY, params, max_slots=2, max_seq=64,
                        prefill_chunks=(4, 8))
    eng.submit(Request(uid="b", prompt=prompt, max_new_tokens=4))
    out = eng.run(max_steps=200)
    np.testing.assert_array_equal(out["b"], want)
    assert eng.stats["prefill_chunks"] == 1


# ---------------------------------------------------------------------------
# chunk-append attention vs naive oracle (dynamic causal boundary)
# ---------------------------------------------------------------------------

def _naive_chunk_attn(q, k, v, prefix, window=None):
    b, c, h, hd = q.shape
    _, s, kvh, _ = k.shape
    g = h // kvh
    qh = q.transpose(0, 2, 1, 3).reshape(b, kvh, g, c, hd)
    sc = jnp.einsum("bkgch,bskh->bkgcs", qh.astype(jnp.float32),
                    k.astype(jnp.float32)) * hd ** -0.5
    kpos = jnp.arange(s)[None, None, :]
    qpos = prefix[:, None, None] + jnp.arange(c)[None, :, None]
    mask = kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    sc = jnp.where(mask[:, None, None], sc, -1e30)
    p = jax.nn.softmax(sc, -1)
    o = jnp.einsum("bkgcs,bskh->bkgch", p, v.astype(jnp.float32))
    return o.reshape(b, h, c, hd).transpose(0, 2, 1, 3).astype(q.dtype)


@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("window", [None, 8])
def test_flash_prefill_chunk_matches_naive(mode, window):
    rng = np.random.default_rng(0)
    B, C, H, KVH, S, hd = 3, 8, 8, 2, 40, 16
    q = jnp.asarray(rng.standard_normal((B, C, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KVH, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KVH, hd)), jnp.float32)
    # prefix 0 (first chunk), mid, and S-C (arena exactly full)
    prefix = jnp.asarray([0, 17, S - C], jnp.int32)
    got = ops.flash_prefill_chunk(q, k, v, prefix=prefix, window=window,
                                  mode=mode, bk=16)
    want = _naive_chunk_attn(q, k, v, prefix, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_prefill_chunk_prefix_is_runtime_data():
    """Same compiled shape must serve every chunk position: jit once, call
    with different prefixes, no retrace."""
    rng = np.random.default_rng(1)
    B, C, H, KVH, S, hd = 1, 4, 4, 4, 32, 8
    q = jnp.asarray(rng.standard_normal((B, C, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KVH, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KVH, hd)), jnp.float32)
    traces = []

    @jax.jit
    def f(q, k, v, prefix):
        traces.append(1)
        return ops.flash_prefill_chunk(q, k, v, prefix=prefix, mode="ref")

    for pre in (0, 4, 20):
        out = f(q, k, v, jnp.asarray([pre], jnp.int32))
        want = _naive_chunk_attn(q, k, v, jnp.asarray([pre], jnp.int32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5)
    assert len(traces) == 1                     # one trace, three prefixes


# ---------------------------------------------------------------------------
# cache insert (slot splice) over fused batch dims
# ---------------------------------------------------------------------------

def test_cache_insert_targets_one_slot_for_fused_batch_dims():
    """cache_insert must overwrite exactly slot ``slot``'s rows (with the
    per-leaf batch factor applied) and leave every other slot bit-equal —
    the contract the engine's donated in-place splice relies on."""
    L, slots, S, kvh, hd, nh = 2, 3, 8, 2, 4, 5
    rng = np.random.default_rng(2)
    big = {
        "kv": jnp.asarray(rng.standard_normal((L, slots, S, kvh, hd)),
                          jnp.float32),
        "ssm": jnp.asarray(rng.standard_normal((L, slots * nh, 7)),
                           jnp.float32),
    }
    one = {
        "kv": jnp.asarray(rng.standard_normal((L, 1, S, kvh, hd)),
                          jnp.float32),
        "ssm": jnp.asarray(rng.standard_normal((L, nh, 7)), jnp.float32),
    }
    for slot in range(slots):
        back = jax.jit(cache_insert)(big, one, jnp.int32(slot))
        np.testing.assert_array_equal(np.asarray(back["kv"][:, slot]),
                                      np.asarray(one["kv"][:, 0]))
        np.testing.assert_array_equal(
            np.asarray(back["ssm"][:, slot * nh:(slot + 1) * nh]),
            np.asarray(one["ssm"]))
        others = [s for s in range(slots) if s != slot]
        np.testing.assert_array_equal(np.asarray(back["kv"][:, others]),
                                      np.asarray(big["kv"][:, others]))


# ---------------------------------------------------------------------------
# model level: chunked prefill ≡ monolithic prefill
# ---------------------------------------------------------------------------

TINY = ArchConfig(name="tiny-dense", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab=97, head_dim=8,
                  param_dtype="float32", act_dtype="float32", max_seq=64)


@pytest.fixture(scope="module")
def tiny_model():
    model = registry.build_model(TINY)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    return model, params


def test_prefill_chunk_matches_monolithic(tiny_model):
    """Ingesting the prompt as bucket-sized chunks into one slot of a
    multi-slot arena writes the same cache rows and yields the same
    last-token logits as one monolithic call — and leaves every other
    slot's rows untouched (the in-place splice is slot-local)."""
    model, params = tiny_model
    rng = np.random.default_rng(3)
    plen, max_seq, slots, slot = 21, 40, 3, 1
    prompt = rng.integers(0, TINY.vocab, plen).astype(np.int32)

    cache_m = model.init_cache(1, max_seq)
    logits_m, cache_m = jax.jit(model.prefill)(
        params, jnp.asarray(prompt)[None], cache_m)

    # arena pre-filled with noise so "other slots untouched" is observable
    cache_c = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        model.init_cache(slots, max_seq))
    before = jax.tree.map(np.asarray, cache_c)
    chunk_fn = jax.jit(model.prefill_chunk)
    start = 0
    for size in chunk_plan(plen, (4, 8)):       # [8, 8, 4, 4(pad 3)]
        chunk = np.zeros((size,), np.int32)
        real = min(size, plen - start)
        chunk[:real] = prompt[start:start + real]
        is_last = start + size >= plen
        last_idx = plen - start - 1 if is_last else 0
        logits_c, cache_c = chunk_fn(params, jnp.asarray(chunk)[None],
                                     cache_c, jnp.int32(slot),
                                     jnp.int32(start),
                                     jnp.int32(last_idx))
        start += size
    np.testing.assert_allclose(np.asarray(logits_c), np.asarray(logits_m),
                               atol=1e-4, rtol=1e-4)
    others = [s for s in range(slots) if s != slot]
    for leaf in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache_c[leaf][:, slot, :plen]),
            np.asarray(cache_m[leaf][:, 0, :plen]), atol=1e-4)
        # rows past the padded plan and every other slot are untouched
        np.testing.assert_array_equal(
            np.asarray(cache_c[leaf][:, others]), before[leaf][:, others])
        np.testing.assert_array_equal(
            np.asarray(cache_c[leaf][:, slot, start:]),
            before[leaf][:, slot, start:])


# ---------------------------------------------------------------------------
# per-family chunked prefill: MoE / SSM / hybrid on the rows/arena contract
# (tiny family configs + the module-scoped ``family_model`` fixture live in
# conftest.py, shared with test_zero_copy so the pinned regime cannot
# drift between suites)
# ---------------------------------------------------------------------------

def test_every_lm_family_supports_chunked_prefill(family_model):
    """The dense-only gates are gone: every family exposes the chunk path
    and the in-place arena decode path (the engine's donation/scheduler
    capabilities key off these flags)."""
    cfg, model, _ = family_model
    assert model.supports_chunked_prefill
    assert model.inplace_arena_decode


def test_engine_still_rejects_models_without_chunk_support(tiny_model):
    """A driver without the chunk hooks (non-LM families) must be refused
    chunked mode up front, not fail inside a traced call."""
    model, params = tiny_model

    class NoChunk:
        supports_chunked_prefill = False
        inplace_arena_decode = False

        def __getattr__(self, name):        # delegate everything else
            return getattr(model, name)

    with pytest.raises(ValueError, match="chunked"):
        ServingEngine(NoChunk(), TINY, params, max_slots=2, max_seq=64,
                      prefill_chunks=(8, 16))


def test_family_prefill_chunk_matches_monolithic(family_model):
    """Chunked ingestion (recurrent-state threading across chunks, padded
    final chunk masked out of the recurrence) reproduces monolithic
    prefill's last-token logits and leaves every other slot's arena state
    untouched — the dense equivalence, per family."""
    cfg, model, params = family_model
    rng = np.random.default_rng(3)
    plen, max_seq, slots, slot = 21, 40, 3, 1
    prompt = rng.integers(0, cfg.vocab, plen).astype(np.int32)

    cache_m = model.init_cache(1, max_seq)
    logits_m, cache_m = jax.jit(model.prefill)(
        params, jnp.asarray(prompt)[None], cache_m)

    cache_c = model.init_cache(slots, max_seq)
    before = jax.tree.map(np.asarray, cache_c)
    chunk_fn = jax.jit(model.prefill_chunk)
    start = 0
    for size in chunk_plan(plen, (4, 8)):
        chunk = np.zeros((size,), np.int32)
        real = min(size, plen - start)
        chunk[:real] = prompt[start:start + real]
        logits_c, cache_c = chunk_fn(params, jnp.asarray(chunk)[None],
                                     cache_c, jnp.int32(slot),
                                     jnp.int32(start), jnp.int32(real - 1))
        start += size
    np.testing.assert_allclose(np.asarray(logits_c), np.asarray(logits_m),
                               atol=1e-4, rtol=1e-4)
    # other slots' rows/state bit-untouched (slot-local writes); the fused
    # SSD leaves carry a per-slot factor f = dim1 // slots
    after = jax.tree.map(np.asarray, cache_c)

    def check_leaf(b, a):
        f = b.shape[1] // slots
        others = [i for s in range(slots) if s != slot
                  for i in range(s * f, (s + 1) * f)]
        np.testing.assert_array_equal(a[:, others], b[:, others])

    jax.tree.map(check_leaf, before, after)


@pytest.mark.parametrize("depth", [0, 2])
def test_family_engine_chunked_matches_sequential(family_model, depth):
    """Chunked prefill interleaved with decode, slots < requests, mixed
    prompt lengths -> token-exact vs sequential monolithic generation for
    MoE (dropless), SSM and hybrid."""
    cfg, model, params = family_model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (5, 9, 7, 12)]
    gens = [8, 6, 10, 7]
    want = [_reference(model, params, p, g) for p, g in zip(prompts, gens)]
    eng = ServingEngine(model, cfg, params, max_slots=2, max_seq=64,
                        depth=depth, prefill_chunks=(4, 8))
    for i, (p, g) in enumerate(zip(prompts, gens)):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=g))
    out = eng.run(max_steps=500)
    for i in range(4):
        np.testing.assert_array_equal(out[i], want[i])
    assert eng.stats["prefills"] == 0           # no monolithic calls
    assert eng.stats["prefill_compiles"] <= 2   # |{4, 8}|


def test_family_engine_chunked_preemption_recompute_is_exact(family_model):
    """Undersized page pool + chunked prefill per family: eviction
    (possibly mid-prefill, discarding chunk-threaded recurrent state)
    rewinds the chunk cursor; the replay re-derives the state and the
    tokens exactly."""
    cfg, model, params = family_model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (10, 12, 11)]
    want = [_reference(model, params, p, 14) for p in prompts]
    eng = ServingEngine(model, cfg, params, max_slots=3, max_seq=64,
                        depth=2, page_size=4, num_pages=8,
                        prefill_chunks=(4, 8))
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=14))
    out = eng.run(max_steps=2000)
    for i in range(3):
        np.testing.assert_array_equal(out[i], want[i])
    assert eng.scheduler.stats["preempted"] > 0


# ---------------------------------------------------------------------------
# engine end-to-end with chunked prefill
# ---------------------------------------------------------------------------

def _reference(model, params, prompt, gen, max_seq=64):
    cache = model.init_cache(1, max_seq)
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


@pytest.mark.parametrize("depth", [0, 2])
def test_engine_chunked_matches_sequential(tiny_model, depth):
    """Chunked prefill interleaved with decode (slots < requests, mixed
    lengths incl. sub-bucket and multi-chunk prompts) -> token-exact vs
    sequential monolithic generation, with compiles capped by the bucket
    set instead of the number of distinct lengths."""
    model, params = tiny_model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, TINY.vocab, n).astype(np.int32)
               for n in (5, 9, 7, 12)]
    gens = [8, 6, 10, 7]
    want = [_reference(model, params, p, g) for p, g in zip(prompts, gens)]
    eng = ServingEngine(model, TINY, params, max_slots=2, max_seq=64,
                        depth=depth, prefill_chunks=(4, 8))
    for i, (p, g) in enumerate(zip(prompts, gens)):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=g))
    out = eng.run(max_steps=500)
    for i in range(4):
        np.testing.assert_array_equal(out[i], want[i])
    assert eng.stats["prefills"] == 0           # no monolithic calls
    assert eng.stats["prefill_chunks"] >= 4
    assert eng.stats["prefill_compiles"] <= 2   # |{4, 8}|, 4 distinct lens
    assert set(eng.stats["ttft_s"]) == {0, 1, 2, 3}
    assert all(t > 0 for t in eng.stats["ttft_s"].values())


def test_engine_chunked_preemption_recompute_is_exact(tiny_model):
    """Undersized page pool + chunked prefill: preemption (possibly mid-
    prefill) rewinds the chunk cursor and recompute replays identical
    tokens."""
    model, params = tiny_model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, TINY.vocab, n).astype(np.int32)
               for n in (10, 12, 11)]
    want = [_reference(model, params, p, 14) for p in prompts]
    eng = ServingEngine(model, TINY, params, max_slots=3, max_seq=64,
                        depth=2, page_size=4, num_pages=8,
                        prefill_chunks=(4, 8))
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=14))
    out = eng.run(max_steps=2000)
    for i in range(3):
        np.testing.assert_array_equal(out[i], want[i])
    assert eng.scheduler.stats["preempted"] > 0


def test_engine_chunked_budget_interleaves_decode(tiny_model):
    """A long prompt must not monopolise the engine: with a one-bucket
    budget, a short request admitted alongside a long one gets its first
    token while the long prompt is still being ingested."""
    model, params = tiny_model
    rng = np.random.default_rng(5)
    long_p = rng.integers(0, TINY.vocab, 40).astype(np.int32)
    short_p = rng.integers(0, TINY.vocab, 4).astype(np.int32)
    want_long = _reference(model, params, long_p, 6)
    want_short = _reference(model, params, short_p, 6)
    eng = ServingEngine(model, TINY, params, max_slots=2, max_seq=64,
                        depth=0, prefill_chunks=(4,), prefill_budget=4)
    eng.submit(Request(uid="long", prompt=long_p, max_new_tokens=6))
    eng.submit(Request(uid="short", prompt=short_p, max_new_tokens=6))
    out = eng.run(max_steps=500)
    np.testing.assert_array_equal(out["long"], want_long)
    np.testing.assert_array_equal(out["short"], want_short)
    # short (1 chunk) must beat long (10 chunks paced 1/step) to its token
    assert eng.stats["ttft_s"]["short"] < eng.stats["ttft_s"]["long"]


def test_engine_chunked_rejects_plan_overflowing_arena(tiny_model):
    model, params = tiny_model
    eng = ServingEngine(model, TINY, params, max_slots=2, max_seq=16,
                        prefill_chunks=(16,))
    # plan for plen=2 pads to 16 = max_seq: fits exactly with max_new=0?
    # no: scheduler takes plen+max_new<=16, engine checks padded 16<=16 ok
    eng.submit(Request(uid="ok", prompt=np.arange(2, dtype=np.int32),
                       max_new_tokens=14))
    # plen=17 would need a 32-row padded plan > max_seq
    with pytest.raises(ValueError):
        eng2 = ServingEngine(model, TINY, params, max_slots=2, max_seq=24,
                             prefill_chunks=(16,))
        eng2.submit(Request(uid="x", prompt=np.arange(17, dtype=np.int32),
                            max_new_tokens=4))


# ---------------------------------------------------------------------------
# scheduler: mid-prefill preemption rewinds the chunk cursor
# ---------------------------------------------------------------------------

def _req(uid, plen=8, max_new=8):
    return Request(uid=uid, prompt=np.arange(plen, dtype=np.int32),
                   max_new_tokens=max_new)


def test_scheduler_chunked_admission_reserves_padded_plan_rows():
    """The final chunk's pad rows are physically written to the slot, so
    admission must account them in the page pool — not just prompt+1."""
    cache = PagedKVCacheManager(64, 4)
    s = Scheduler(2, cache, chunked=True)
    s.submit(_req("a", plen=9), chunk_plan=[8, 8])      # padded to 16
    (st,) = s.schedule()
    assert cache.length(st.slot) == 16                  # not 10
    # worst-case admission check also covers the padded plan: a plan wider
    # than the whole pool is rejected at submit
    small = Scheduler(1, PagedKVCacheManager(2, 4), chunked=True)
    with pytest.raises(ValueError):
        small.submit(_req("x", plen=5, max_new=1), chunk_plan=[16])


def test_scheduler_chunked_admission_enters_prefilling():
    s = Scheduler(2, PagedKVCacheManager(64, 4), chunked=True)
    s.submit(_req("a"))
    (st,) = s.schedule()
    assert st.status == Status.PREFILLING
    assert s.finish_prefill(st.slot) is st
    assert st.status == Status.RUNNING
    with pytest.raises(ValueError):
        s.finish_prefill(st.slot)               # already running


def test_scheduler_mid_prefill_preemption_rewinds_cursor():
    """A PREFILLING victim must rewind its chunk cursor deterministically:
    re-admission replays the identical chunk sequence from position 0."""
    # 2 slots, 6 pages of 4 rows: both 8-row prompts reserve 3 pages
    s = Scheduler(2, PagedKVCacheManager(6, 4), chunked=True)
    old = s.submit(_req("old", plen=8, max_new=8))
    young = s.submit(_req("young", plen=8, max_new=8))
    assert len(s.schedule()) == 2
    # engine ingested two chunks of the young request, then finished the
    # old one's prefill and started decoding it
    young.chunk_plan = [4, 4]
    young.chunk_idx = 1
    young.prefill_pos = 4
    s.finish_prefill(old.slot)
    for tok in range(3):
        assert s.on_token(old.slot, tok) == []
    deps = s.on_token(old.slot, 99)             # growth -> evict youngest
    assert [st.request.uid for _, st in deps] == ["young"]
    assert young.status == Status.WAITING
    assert young.chunk_idx == 0                 # cursor rewound
    assert young.prefill_pos == 0
    assert young.chunk_plan == [4, 4]           # plan kept (deterministic)
    assert young.slot is None and young.generated == []
    assert old.status == Status.RUNNING         # oldest never evicted


# ---------------------------------------------------------------------------
# run() step accounting + stats reporting satellites
# ---------------------------------------------------------------------------

def test_engine_run_max_steps_is_exact(tiny_model):
    """run(max_steps=N) must execute at most N engine steps (the PR-1 code
    permitted N+1) and still raise when the work cannot converge."""
    model, params = tiny_model
    eng = ServingEngine(model, TINY, params, max_slots=1, max_seq=64)
    eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=50))
    calls = []
    orig = eng.step
    eng.step = lambda: (calls.append(1), orig())[1]
    with pytest.raises(RuntimeError, match="did not converge in 3"):
        eng.run(max_steps=3)
    assert len(calls) == 3


def test_first_token_time_survives_preemption_recompute(tiny_model):
    """TTFT must record the *original* first token, not the recompute's:
    a preempted request re-prefills and re-samples, but its service time
    already started ticking at submit."""
    import time as _time
    model, params = tiny_model
    eng = ServingEngine(model, TINY, params, max_slots=2, max_seq=64)
    st = eng.submit(Request(uid="r", prompt=np.arange(4, dtype=np.int32),
                            max_new_tokens=4))
    eng._first_token(st)
    first = st.ttft_s
    at = st.first_token_at
    assert first is not None and eng.stats["ttft_s"]["r"] == first
    assert first == at - st.submitted_at
    _time.sleep(0.01)
    eng._first_token(st)                        # recompute after preemption
    assert st.ttft_s == first                   # not overwritten
    assert st.first_token_at == at
    assert eng.stats["ttft_s"]["r"] == first


def test_engine_chunked_oldest_not_starved_by_fresh_arrivals(tiny_model):
    """Alternating chunk order: a long prompt mid-ingestion keeps making
    progress (and finishes) even when every other step hands the budget to
    a fresher pos-0 arrival."""
    model, params = tiny_model
    rng = np.random.default_rng(8)
    long_p = rng.integers(0, TINY.vocab, 36).astype(np.int32)
    shorts = [rng.integers(0, TINY.vocab, 4).astype(np.int32)
              for _ in range(6)]
    want_long = _reference(model, params, long_p, 4)
    want_shorts = [_reference(model, params, p, 4) for p in shorts]
    eng = ServingEngine(model, TINY, params, max_slots=2, max_seq=64,
                        depth=0, prefill_chunks=(4,), prefill_budget=4)
    eng.submit(Request(uid="long", prompt=long_p, max_new_tokens=4))
    for i, p in enumerate(shorts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    out = eng.run(max_steps=500)
    np.testing.assert_array_equal(out["long"], want_long)
    for i in range(6):
        np.testing.assert_array_equal(out[i], want_shorts[i])
    # the long prompt (9 chunks at 1 chunk/step shared) must not be the
    # absolute last to finish prefill behind all 6 shorts' admissions
    assert eng.stats["ttft_s"]["long"] < max(
        eng.stats["ttft_s"][i] for i in range(6))


def test_report_stats_greedy_only_prints_na_not_nan(tiny_model, capsys):
    """serve.py's sampler stats line averages sampling steps over
    ``sampled_requests`` — a greedy-only run (--sampling-mix 0) has zero
    of those and used to print nan/raise ZeroDivisionError; it must say
    n/a instead (and still print the real average when sampling)."""
    from repro.launch.serve import report_stats
    from repro.runtime.serving import SamplingParams
    model, params = tiny_model
    eng = ServingEngine(model, TINY, params, max_slots=2, max_seq=64)
    eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=3))
    eng.run(max_steps=200)
    report_stats(eng)                          # greedy-only: must not raise
    out = capsys.readouterr().out
    assert "n/a (greedy-only run)" in out
    assert "nan" not in out
    eng2 = ServingEngine(model, TINY, params, max_slots=2, max_seq=64)
    eng2.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                        max_new_tokens=3,
                        sampling=SamplingParams(temperature=0.7, seed=1)))
    eng2.run(max_steps=200)
    report_stats(eng2)
    out = capsys.readouterr().out
    assert "steps/request" in out and "n/a" not in out


def test_engine_stats_track_prefill_compiles_monolithic(tiny_model):
    """Monolithic mode: one distinct compile-cache entry per distinct
    prompt length (the churn chunking bounds)."""
    model, params = tiny_model
    rng = np.random.default_rng(6)
    eng = ServingEngine(model, TINY, params, max_slots=2, max_seq=64)
    for i, n in enumerate((5, 9, 5, 12)):       # 3 distinct lengths
        eng.submit(Request(uid=i,
                           prompt=rng.integers(0, TINY.vocab, n)
                           .astype(np.int32), max_new_tokens=3))
    eng.run(max_steps=500)
    assert eng.stats["prefill_compiles"] == 3
    assert eng.stats["prefills"] == 4
    assert set(eng.stats["ttft_s"]) == {0, 1, 2, 3}
