"""The dropless expert layer on the serving path, on seeded random weights
at a tiny size: the program against the plain reference of the benchmark
(``chipbench/models/moe.py``) through prefill and decode, the expert-
parallel share arithmetic, batch invariance, the engine's routing
counters, and the grouped-matmul kernel against its oracle."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec
from repro.configs.base import ArchConfig, MoEConfig
from repro.kernels import expert_gmm as egmm
from repro.kernels import ops, ref
from repro.models import moe as M
from repro.models import registry
from repro.models.layers import PARKED_POS
from repro.runtime.serving import EngineConfig, Request, ServingEngine

TINY_MOE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "chipbench", "data", "tiny-moe.json")


@pytest.fixture(scope="module")
def share():
    """The tiny Qwen3-MoE share (experts 4-7 of 16, top-4), its program
    model and seeded weights, and the reference module."""
    with open(TINY_MOE) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny-moe"
    family = spec.model_module("moe")
    arch = family.program_config(cfg)
    model = registry.build_model(arch)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = family.make_params(cfg, shapes, 2 ** 33 + 5)
    return cfg, family, arch, model, params


def test_program_matches_reference_through_prefill_and_decode(share):
    """Chunked prefill then decode through the arena: every step's logits
    equal the reference's full forward at that position."""
    cfg, family, arch, model, params = share
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, arch.vocab, 45).astype(np.int32)
    cache = model.init_cache(2, 96)
    chunk = jax.jit(model.prefill_chunk)
    got, start = [], 0
    for size in (32, 32):
        toks = np.zeros(size, np.int32)
        real = min(size, prompt.size - start)
        toks[:real] = prompt[start:start + real]
        logits, cache = chunk(params, jnp.asarray(toks)[None], cache,
                              jnp.int32(1), jnp.int32(start),
                              jnp.int32(real - 1))
        start += size
    got.append(np.asarray(logits[0]))
    seq = list(prompt) + [int(np.argmax(got[-1]))]
    step = jax.jit(model.decode_step)
    for _ in range(6):
        pos = jnp.asarray([PARKED_POS, len(seq) - 1], jnp.int32)
        logits, cache = step(params, jnp.asarray([0, seq[-1]], jnp.int32),
                             cache, pos)
        got.append(np.asarray(logits[1]))
        seq.append(int(np.argmax(got[-1])))
    rows = np.arange(prompt.size - 1, len(seq) - 1)
    want = np.asarray(family.logits(cfg, params, np.asarray(seq), rows))
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=2e-4)


def _layer_cfg(held, first):
    return ArchConfig(name="t", family="moe", n_layers=1, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=16, vocab=64,
                      moe=MoEConfig(n_experts=16, top_k=4, d_ff_expert=16,
                                    experts_held=held, first_expert=first),
                      param_dtype="float32", act_dtype="float32")


def _share_params(p, first, held):
    return dict(p, experts=jax.tree.map(lambda a: a[first:first + held],
                                        p["experts"]))


def test_expert_shares_add_up_to_the_uncut_layer():
    """16 experts split into 8 shares of 2 (expert parallelism): the
    shares' layer outputs add up to the uncut layer, and their counters
    to its counters."""
    full = _layer_cfg(0, 0)
    p = M.moe_mlp_init(jax.random.PRNGKey(3), full)
    x = jax.random.normal(jax.random.PRNGKey(4), (40, 32))
    valid = jnp.arange(40) < 37                     # 3 rows of padding
    y, counts = M.moe_mlp_serve(p, full, x, valid)
    parts = [M.moe_mlp_serve(_share_params(p, 2 * i, 2), _layer_cfg(2, 2 * i),
                             x, valid) for i in range(8)]
    np.testing.assert_allclose(sum(np.asarray(y_) for y_, _ in parts),
                               np.asarray(y), atol=1e-5)
    assert int(counts[0]) == 37 * 4                 # every valid choice
    assert sum(int(c[0]) for _, c in parts) == 37 * 4
    assert sum(int(c[1]) for _, c in parts) == int(counts[1]) <= 16
    np.testing.assert_array_equal(np.asarray(y)[37:], 0.0)


def test_slot_logits_do_not_depend_on_batch_mates(share):
    """Dropless: a slot decoding alone and beside seven others gets the
    same logits (capacity dispatch couples the two)."""
    cfg, family, arch, model, params = share
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, arch.vocab, 11 + 3 * i).astype(np.int32)
               for i in range(8)]
    chunk = jax.jit(model.prefill_chunk)
    step = jax.jit(model.decode_step)

    def decode(batch):
        cache = model.init_cache(len(batch), 64)
        toks, pos = [], []
        for slot, pr in enumerate(batch):
            padded = np.zeros(32, np.int32)
            padded[:pr.size] = pr
            logits, cache = chunk(params, jnp.asarray(padded)[None], cache,
                                  jnp.int32(slot), jnp.int32(0),
                                  jnp.int32(pr.size - 1))
            toks.append(int(np.argmax(np.asarray(logits[0]))))
            pos.append(pr.size)
        logits, _ = step(params, jnp.asarray(toks, jnp.int32), cache,
                         jnp.asarray(pos, jnp.int32))
        return np.asarray(logits[0])

    np.testing.assert_allclose(decode(prompts[:1]), decode(prompts),
                               atol=1e-6, rtol=1e-6)


def test_engine_counts_rows_routed_here(share):
    """The engine's counters, read with each step's tokens: decode rows
    are top-k per live slot per layer when every expert is held; chunk
    rows top-k per valid prompt token; a share holds fewer."""
    cfg, family, arch, model, params = share
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, arch.vocab, n).astype(np.int32)
               for n in (7, 19, 12)]
    full = dataclasses.replace(arch, moe=dataclasses.replace(
        arch.moe, experts_held=0, first_expert=0))
    full_model = registry.build_model(full)
    full_params = jax.jit(full_model.init)(jax.random.PRNGKey(1))
    stats = {}
    for name, (a, m, p) in {"full": (full, full_model, full_params),
                            "share": (arch, model, params)}.items():
        eng = ServingEngine(m, a, p, config=EngineConfig(
            max_slots=2, max_seq=64, depth=2, prefill_chunks=(8, 16)))
        live = []
        submit = eng._queue.submit

        def counting_submit(state, eng=eng, submit=submit, live=live):
            live.append(int((eng._host_pos < PARKED_POS).sum()))
            return submit(state)

        eng._queue.submit = counting_submit
        for i, pr in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=pr, max_new_tokens=5))
        eng.run()
        stats[name] = (dict(eng.stats), sum(live))
    (s, live), k, layers = stats["full"], arch.moe.top_k, arch.n_layers
    assert s["decode_moe_rows_here"] == k * layers * live > 0
    assert (s["moe_rows_here"] - s["decode_moe_rows_here"]
            == k * layers * sum(pr.size for pr in prompts))
    assert 0 < s["decode_moe_experts_touched"] <= \
        layers * arch.moe.n_experts * s["decode_steps"]
    sh, _ = stats["share"]
    assert 0 < sh["decode_moe_rows_here"] < s["decode_moe_rows_here"]
    assert sh["decode_moe_experts_touched"] <= \
        layers * arch.moe.held * sh["decode_steps"]


@pytest.mark.parametrize("sizes,m,k,n", [
    ([3, 0, 5, 1], 9, 32, 48),              # empty group, ragged row edge
    ([0, 0, 0], 5, 32, 128),                # no rows at all
    ([1], 1, 32, 128),                      # one row
    ([20, 0, 7, 0, 30], 61, 128, 256),      # groups spanning row tiles
    ([0, 2, 1, 1, 3, 0, 1, 0], 200, 64, 128),   # decode-like: sparse rows
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_expert_gmm_matches_oracle(sizes, m, k, n, dtype):
    g = len(sizes)
    sizes = jnp.asarray(sizes, jnp.int32)
    kl, kr = jax.random.split(jax.random.PRNGKey(sum(sizes.tolist()) + m))
    lhs = jax.random.normal(kl, (m, k)).astype(dtype)
    rhs = jax.random.normal(kr, (g, k, n)).astype(dtype)
    got = ops.expert_gmm(lhs, rhs, sizes, mode="interpret")
    want = ref.expert_gmm(lhs, rhs, sizes)
    rows = int(sizes.sum())
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got[:rows], np.float32),
                               np.asarray(want[:rows]), atol=tol * k ** 0.5,
                               rtol=tol)
    np.testing.assert_array_equal(np.asarray(want[rows:]), 0.0)


def test_expert_gmm_reads_one_layer_of_a_stack():
    """Weights stacked over layers, ``(L, G, K, N)``, read at ``layer``
    (a traced index, as a layer scan carries it) give that layer's
    product on the kernel and the ``ref`` path."""
    sizes = jnp.asarray([2, 0, 5, 1], jnp.int32)
    kl, kr = jax.random.split(jax.random.PRNGKey(5))
    lhs = jax.random.normal(kl, (9, 32))
    rhs = jax.random.normal(kr, (3, 4, 32, 48))
    want = ref.expert_gmm(lhs, rhs[2], sizes)
    for mode in ("interpret", "ref"):
        got = jax.jit(lambda li: ops.expert_gmm(lhs, rhs, sizes, layer=li,
                                                mode=mode))(jnp.int32(2))
        np.testing.assert_allclose(np.asarray(got[:8]),
                                   np.asarray(want[:8]), atol=1e-4,
                                   rtol=1e-4)


@pytest.mark.parametrize("sizes,m", [
    ([0, 2, 1, 1, 3, 0, 1, 0, 0, 0, 4, 0, 2, 0, 0, 1], 192),
    ([40, 0, 0, 77, 0, 1, 0, 200], 4096),
    ([0] * 16, 192),
])
def test_expert_gmm_fetches_no_weights_of_an_expert_without_rows(sizes, m):
    """The grid runs ``n_work`` items; reading the weight block each one's
    index map asks for, every expert with rows is fetched once per column
    tile (its items are consecutive) and no expert without rows ever is.
    Every routed row's tile is visited for its expert."""
    sizes = np.asarray(sizes, np.int32)
    tm = egmm.row_tile(m)
    offsets, groups, tiles, n_work = jax.tree.map(
        np.asarray, egmm.schedule(jnp.asarray(sizes), m, tm))
    layer = np.asarray([3], np.int32)
    blocks = [egmm._rhs_block(0, w, layer, offsets, groups, tiles)
              for w in range(int(n_work))]
    assert {b[0] for b in blocks} <= {3}            # the layer asked for
    fetched = [b[1] for b in blocks]
    runs = [gid for i, gid in enumerate(fetched)
            if i == 0 or gid != fetched[i - 1]]
    assert runs == [g for g in range(sizes.size) if sizes[g] > 0]
    visited = {(int(groups[w]), int(tiles[w])) for w in range(int(n_work))}
    for g in range(sizes.size):
        for r in range(offsets[g], offsets[g + 1]):
            assert (g, r // tm) in visited
    assert len(visited) == int(n_work)          # no item twice
    out_tiles = [egmm._out_block(0, w, layer, offsets, groups, tiles)[0]
                 for w in range(int(n_work))]
    assert out_tiles == sorted(out_tiles)       # an output tile's visits
    #                                             are consecutive
