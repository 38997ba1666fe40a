"""Engine spans, waits, counters and request timestamps.

The serving engine marks its phases with ``serving.*`` host spans in the
JAX profiler's trace (``repro.core.spans``); these tests record a trace of
the tiny engine on the CPU and read it back with ``ProfileData``.  They
also pin the engine's request timestamps (``admitted_at``,
``first_token_at``) across preemption recompute, the per-step counters,
and that every host block counts into ``host_blocked_s``.
"""
import gc
import glob
import itertools
import os

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core import spans
from repro.models import registry
from repro.runtime.serving import (EngineConfig, Request, ServingEngine,
                                   SpecConfig)

TINY = ArchConfig(name="tiny-spans", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab=97,
                  param_dtype="float32", act_dtype="float32", max_seq=64)
DRAFT = ArchConfig(name="tiny-spans-draft", family="dense", n_layers=1,
                   d_model=16, n_heads=2, n_kv_heads=1, d_ff=32, vocab=97,
                   head_dim=8, param_dtype="float32", act_dtype="float32",
                   max_seq=64)
ENGINE_SPANS = ("serving.step", "serving.retire", "serving.admit",
                "serving.prefill", "serving.chunk", "serving.decode",
                "serving.wait")


@pytest.fixture(scope="module")
def tiny_model():
    model = registry.build_model(TINY)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    return model, params


def _prompts(n, lengths=(12, 5, 9, 7)):
    rng = np.random.default_rng(0)
    return [rng.integers(0, TINY.vocab, lengths[i % len(lengths)])
            .astype(np.int32) for i in range(n)]


def _serve(model, params, config, n=4, max_new=5):
    eng = ServingEngine(model, TINY, params, config=config)
    states = [eng.submit(Request(uid=f"r{i}", prompt=p,
                                 max_new_tokens=max_new))
              for i, p in enumerate(_prompts(n))]
    eng.run(max_steps=500)
    return eng, states


def _host_events(trace_dir):
    """``(start, end, name, stats)`` of every ``serving.*`` host event."""
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serving."):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tiny_model, tmp_path_factory):
    """One chunked engine at depth 0 (so the dispatch queue's backpressure
    blocks), one speculative engine, and a forced collection, all inside
    one profiler session."""
    model, params = tiny_model
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        eng, _ = _serve(model, params, EngineConfig(
            max_slots=2, max_seq=64, depth=0, prefill_chunks=(4, 8)))
        spec_eng, _ = _serve(model, params, EngineConfig(
            max_slots=2, max_seq=64,
            speculative=SpecConfig(draft=DRAFT, k=2, adaptive=False)), n=2)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    return _host_events(d), eng, spec_eng


def test_engine_phases_are_spans(traced):
    events, _, _ = traced
    names = {name for _, _, name, _ in events}
    for name in ENGINE_SPANS + ("serving.spec_round", "serving.gc"):
        assert name in names, name
    whats = {st["what"] for _, _, name, st in events
             if name == "serving.wait"}
    assert {"backpressure", "readback", "first_token",
            "spec_readback"} <= whats
    steps = [st for _, _, name, st in events if name == "serving.step"]
    assert all(isinstance(st["tick"], int) for st in steps)
    assert all(st["k"] == 2 for _, _, name, st in events
               if name == "serving.spec_round")


def test_waits_nest_inside_steps(traced):
    events, _, _ = traced
    steps = [(a, b) for a, b, name, _ in events if name == "serving.step"]
    waits = [(a, b, st["what"]) for a, b, name, st in events
             if name == "serving.wait"]
    inside = [w for w in waits
              if any(a <= w[0] and w[1] <= b for a, b in steps)]
    # every wait but the final drain of run() happens inside a step
    assert len(inside) >= len(waits) - 2
    assert {w[2] for w in inside} >= {"readback", "first_token",
                                      "backpressure"}


def test_chunk_spans_carry_their_request(traced):
    events, eng, _ = traced
    chunks = [st for _, _, name, st in events if name == "serving.chunk"]
    assert len(chunks) == eng.stats["prefill_chunks"]
    for st in chunks:
        assert st["uid"] in {f"r{i}" for i in range(4)}
        assert st["size"] in (4, 8)
        assert 0 < st["valid"] <= st["size"]
    # the 12-token prompt arrives as chunks of 8 and 4 real tokens
    assert sorted((c["size"], c["valid"]) for c in chunks
                  if c["uid"] == "r0") == [(4, 4), (8, 8)]


def test_gc_collection_is_a_span(traced):
    events, _, _ = traced
    gcs = [st for _, _, name, st in events if name == "serving.gc"]
    assert any(st["generation"] == 2 for st in gcs)


def test_blocked_counter_and_wait_spans_agree(traced):
    """``host_blocked_s`` and the ``serving.wait`` spans time the same
    blocks: the counter is the spans' total less their bookkeeping."""
    events, eng, _ = traced
    spans_s = sum((b - a) for a, b, name, _ in events
                  if name == "serving.wait"
                  and a >= min(a for a, _, n, _ in events
                               if n == "serving.step")) / 1e9
    total = eng.stats["host_blocked_s"]
    assert 0 < total <= spans_s * 1.5 + 1e-3


def test_backpressure_counts_into_host_blocked(tiny_model, monkeypatch):
    """Under blocking dispatch (depth 0) the queue's block_until_ready is a
    host wait like the readbacks, and adds to the engine's counter."""
    seen = []
    real = spans.wait

    def recording(what, counts):
        seen.append((what, counts))
        return real(what, counts)

    monkeypatch.setattr(spans, "wait", recording)
    model, params = tiny_model
    eng, _ = _serve(model, params, EngineConfig(max_slots=2, max_seq=64,
                                                depth=0), n=2)
    back = [c for w, c in seen if w == "backpressure"]
    assert len(back) == eng.stats["decode_steps"]
    assert all(c is eng.stats for c in back)
    assert eng.stats["host_blocked_s"] > 0


def test_request_timestamps_and_ttft(tiny_model):
    model, params = tiny_model
    eng, states = _serve(model, params, EngineConfig(
        max_slots=2, max_seq=64, prefill_chunks=(4, 8)))
    for st in states:
        assert st.submitted_at <= st.admitted_at <= st.first_token_at
        assert st.ttft_s == st.first_token_at - st.submitted_at


def test_timestamps_keep_first_values_across_preemption(tiny_model):
    """An undersized page pool preempts and recomputes; each request keeps
    the clock readings of its first admission and first token, as ttft_s
    keeps its first value."""
    model, params = tiny_model
    tick = itertools.count()
    eng = ServingEngine(model, TINY, params, clock=lambda: float(next(tick)),
                        config=EngineConfig(max_slots=3, max_seq=64,
                                            page_size=4, num_pages=8))
    states = [eng.submit(Request(uid=i, prompt=p, max_new_tokens=14))
              for i, p in enumerate(_prompts(3, (10, 12, 11)))]
    first = {}
    for _ in range(2000):
        if eng.scheduler.all_done:
            break
        eng.step()
        for st in states:
            if st.first_token_at is not None and st.request.uid not in first:
                first[st.request.uid] = (st.admitted_at, st.first_token_at,
                                         st.ttft_s)
    assert eng.scheduler.stats["preempted"] > 0
    recomputed = [st for st in states if st.prefills > 1]
    assert recomputed
    for st in states:
        assert (st.admitted_at, st.first_token_at, st.ttft_s) \
            == first[st.request.uid]


def test_step_and_prefill_slot_counters(tiny_model):
    """One 12-token prompt in chunks of 4, one chunk per step: it holds
    its slot PREFILLING on three steps."""
    model, params = tiny_model
    eng = ServingEngine(model, TINY, params, config=EngineConfig(
        max_slots=2, max_seq=64, prefill_chunks=(4,), prefill_budget=4))
    eng.submit(Request(uid=0, prompt=_prompts(1)[0], max_new_tokens=3))
    n = 0
    while not eng.scheduler.all_done:
        eng.step()
        n += 1
    assert eng.stats["steps"] == n
    assert eng.stats["slot_steps_prefilling"] == 3
