"""flash_decode reads the stacked KV arena in place: kernel parity with the
``_flash_decode_ref`` oracle over a patched layer view, the engine's strip
counter, and greedy streams of the served engine on the kernel path
against the ``ref`` path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, tiny_family_configs
from repro.core import kv_format as kvf
from repro.kernels import flash_decode as fd
from repro.kernels import ops
from repro.models import registry
from repro.models.layers import PARKED_POS
from repro.runtime.serving import tolerance
from repro.runtime.serving.config import EngineConfig
from repro.runtime.serving.engine import ServingEngine
from repro.runtime.serving.request import Request

# a stacked arena whose rows are not a multiple of the strip: 9 strips + 3
NL, B, KVH, G, HD, BK = 3, 3, 2, 3, 16, 8
ROWS = 9 * BK + 3

ARENA_CASES = {
    "f32": {},
    "bf16": {"dtype": jnp.bfloat16},
    "int8": {"kv_format": "int8"},
    "layer_first": {"layer": 0},
    "layer_last": {"layer": NL - 1},
    "pos_zero": {"pos": [0, 0, 9]},                   # new row only
    "pos_last_row": {"pos": [ROWS - 1, 5, ROWS - 1]},
    "parked": {"pos": [PARKED_POS, 12, PARKED_POS]},
    "window": {"window": 11},
    "strip_edges": {"pos": [BK, 2 * BK - 1, 9 * BK]},
    # the generic path (enc-dec cross-attention): a per-layer cache, no
    # new row, ``lengths=None`` attends every row
    "no_lengths": {"generic": True},
}


def _arena(rng, dtype, kv_format):
    shape = (NL, B, ROWS, KVH, HD)
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    if kv_format is None:
        return k.astype(dtype), v.astype(dtype), None, None
    fmt = kvf.get(kv_format)
    (kq, ks), (vq, vs) = kvf.quantize(fmt, k), kvf.quantize(fmt, v)
    return kq, vq, ks, vs


def _new_row(rng, dtype, kv_format):
    k = jnp.asarray(rng.standard_normal((B, KVH, HD)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, KVH, HD)), jnp.float32)
    if kv_format is None:
        return k.astype(dtype), v.astype(dtype)
    fmt = kvf.get(kv_format)
    (kq, ks), (vq, vs) = kvf.quantize(fmt, k), kvf.quantize(fmt, v)
    return kq, vq, ks, vs


@pytest.mark.parametrize("mode", ["interpret", "ref"])
@pytest.mark.parametrize("case", sorted(ARENA_CASES))
def test_flash_decode_reads_arena_in_place(case, mode):
    """Layer ``layer`` of the stacked arena plus a separate new row equals
    the oracle over that layer's view with the row written at ``pos``."""
    c = ARENA_CASES[case]
    dtype, kv_format = c.get("dtype", jnp.float32), c.get("kv_format")
    layer, window = c.get("layer", 1), c.get("window")
    pos = jnp.asarray(c.get("pos", [3, 40, ROWS - 2]), jnp.int32)
    rng = np.random.default_rng(sorted(ARENA_CASES).index(case))
    k, v, ks, vs = _arena(rng, dtype, kv_format)
    row = _new_row(rng, dtype, kv_format)
    q = jnp.asarray(rng.standard_normal((B, KVH * G, HD)), jnp.float32)
    live = jnp.where(pos < ROWS, pos, 0)
    qg = q.reshape(B, KVH, G, HD)

    if c.get("generic"):
        got = ops.flash_decode(q, k[layer], v[layer], bk=BK, mode=mode)
        want = ops._flash_decode_ref(qg, k[layer], v[layer],
                                     lengths=jnp.full((B,), ROWS, jnp.int32),
                                     window=None, scale=None, bk=BK)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(want).reshape(got.shape),
                                   atol=2e-5)
        return
    got = ops.flash_decode(q, k, v, lengths=live, layer=jnp.int32(layer),
                           new_row=row, window=window, k_scale=ks,
                           v_scale=vs, bk=BK, mode=mode)

    bidx = jnp.arange(B)
    kl, vl = k[layer].at[bidx, live].set(row[0]), \
        v[layer].at[bidx, live].set(row[1])
    ksl = vsl = None
    if kv_format is not None:
        ksl = ks[layer].at[bidx, live].set(row[2])
        vsl = vs[layer].at[bidx, live].set(row[3])
    want = ops._flash_decode_ref(qg, kl, vl,
                                 lengths=live + 1, window=window, scale=None,
                                 bk=BK, k_scale=ksl, v_scale=vsl)
    got = np.asarray(got)
    assert np.isfinite(got).all()          # parked slots too
    np.testing.assert_allclose(got, np.asarray(want).reshape(got.shape),
                               atol=2e-5)


def test_strip_counts_fetch_live_strips_only():
    live = np.array([0, 1, BK, BK + 1, ROWS])
    fetched, grid = fd.strip_counts(live, ROWS, bk=BK)
    assert fetched == 0 + 1 + 1 + 2 + 10
    assert grid == len(live) * 10
    # an arena shorter than the strip is one strip per live slot
    assert fd.strip_counts(np.array([0, 5]), 6, bk=BK) == (1, 2)


TINY = ArchConfig(name="tiny-dense", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab=97, head_dim=8,
                  param_dtype="float32", act_dtype="float32", max_seq=64)


def _prompts(cfg, n=5):
    rng = np.random.default_rng(3)
    return [rng.integers(0, cfg.vocab, 6 + 5 * i).astype(np.int32)
            for i in range(n)]


@pytest.fixture
def kernel_mode():
    prev = ops.get_mode()
    yield ops.set_mode
    ops.set_mode(prev)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_engine_greedy_streams_kernel_path_match_ref(family, kernel_mode):
    """Greedy streams of the served engine with the Pallas bodies
    (interpret mode) match the ``ref`` path token for token.  Retired and
    idle slots park at ``PARKED_POS`` and decode alongside."""
    cfg = TINY if family == "dense" else tiny_family_configs()["moe"]
    config = EngineConfig(max_slots=3, max_seq=64, depth=1, page_size=8,
                          prefill_chunks=(8, 16))
    streams = {}
    for mode in ("ref", "interpret"):
        kernel_mode(mode)
        model = registry.build_model(cfg)      # fresh compiled-step memo
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
        streams[mode] = tolerance.serve_streams(
            model, cfg, params, _prompts(cfg), max_new_tokens=7,
            config=config)
    report = tolerance.compare_streams(streams["ref"], streams["interpret"])
    assert report.requests == 5 and report.positions == 35
    assert report.identical, report.describe()


def test_engine_counts_kv_strips_from_host_positions():
    """``decode_kv_strips`` sums, over decode steps, the strips the kernel
    fetches per layer: with every slot short of one strip, one per slot
    decoding, none for a retired (parked) or never-used slot."""
    model = registry.build_model(TINY)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    eng = ServingEngine(model, TINY, params, config=EngineConfig(
        max_slots=3, max_seq=64, depth=1, page_size=8))
    for i, p in enumerate(_prompts(TINY, 4)):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=4 + i))
    live_slots = []
    submit = eng._queue.submit

    def counting_submit(state):
        live_slots.append(int((eng._host_pos < PARKED_POS).sum()))
        return submit(state)

    eng._queue.submit = counting_submit
    eng.run()
    assert eng.stats["decode_steps"] == len(live_slots) > 0
    assert eng.stats["decode_kv_strips"] == sum(live_slots)
    assert max(live_slots) == 3 and min(live_slots) < 3
    assert (eng._host_pos == PARKED_POS).all()
    assert not eng._host_active.any()
