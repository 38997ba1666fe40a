"""Compile the served Pallas kernels and the llama3.2-3b serving steps for a
TPU v5e that is described, not attached.

The TPU compiler ships with jax's TPU support, so these tests need no chip:
they lower and compile for ``v5e:2x2`` and assert what only that compiler
can tell — the kernels meet Mosaic's tiling rules at real widths
(llama3.2-3b for attention, mamba2-2.7b for SSD), the steps keep the
kernels (``tpu_custom_call``) and the decode step fits one chip's memory.
Nothing runs, so nothing here says anything about results or times.

The topology is described inside a module fixture (never at import): only
one process may hold the TPU library, and every test worker imports every
test file.  The persistent compilation cache is off around these compiles,
because entries written for a described chip cannot be read back without
one.
"""
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 10**9

# llama3.2-3b serving widths: 8 slots x 4096 rows, 8 KV heads of 128, 3
# query heads per KV head
SLOTS, ROWS, KVH, GROUP, HD = 8, 4096, 8, 3, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # keep the TPU compiler's logs out of the temp directory
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas_mode():
    """Trace the model's ops on the kernel path (``auto`` resolves to
    ``ref`` on this CPU host)."""
    from repro.kernels import ops
    prev = ops.get_mode()
    ops.set_mode("pallas")
    yield
    ops.set_mode(prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _abstract(sharding, tree):
    return jax.tree.map(lambda x: _spec(sharding, x.shape, x.dtype), tree)


_HLO_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
              "u8": 1, "pred": 1}


def _hlo_outputs(text):
    """(name and opcode, shape, bytes) of every array-valued instruction
    in an HLO module's text."""
    for m in re.finditer(r"%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(",
                         text):
        name, dt, dims, op = m.groups()
        n = _HLO_BYTES.get(dt, 4)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        yield f"{name} {op}", dims, n


def _compile(fn, *args, donate=()):
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_flash_decode_compiles(one_chip, kv_dtype):
    """The decode kernel over a stacked 2-layer arena read in place, rows
    not a multiple of the strip, the new row as its own operand."""
    from repro.kernels import flash_decode as fd
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    rows = 9 * 512 + 33
    row_dt = jnp.float32 if kv_dtype == "int8" else kv_dtype
    args = [s((SLOTS, KVH * GROUP, HD), jnp.bfloat16),
            s((2, SLOTS, rows, KVH, HD), kv_dtype),
            s((2, SLOTS, rows, KVH, HD), kv_dtype),
            s((SLOTS,), jnp.int32), s((), jnp.int32),
            s((SLOTS, KVH, HD), row_dt), s((SLOTS, KVH, HD), row_dt)]
    if kv_dtype == "int8":
        args += [s((2, SLOTS, rows, KVH), jnp.float32)] * 2
    compiled = _compile(lambda q, k, v, n, li, kr, vr, *sc: fd.flash_decode(
        q, k, v, n, li, rows=(kr, vr), scales=sc or None), *args)
    # the arena reaches the kernel as it is stored: no copy of it
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("chunk", [32, 512])
def test_flash_prefill_chunk_compiles(one_chip, chunk):
    from repro.kernels import flash_prefill_chunk as fpc
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    _compile(fpc.flash_prefill_chunk,
             s((KVH, GROUP, chunk, HD), jnp.bfloat16),
             s((KVH, ROWS, HD), jnp.bfloat16),
             s((KVH, ROWS, HD), jnp.bfloat16), s((KVH,), jnp.int32))


def test_flash_attention_compiles(one_chip):
    """Monolithic prefill of a ragged 300-token prompt (24 heads): queries
    and keys pad to different block multiples, keys past 300 masked."""
    from repro.kernels import ops
    s = lambda shape: _spec(one_chip, shape, jnp.bfloat16)
    _compile(lambda q, k, v: ops.attention(q, k, v, mode="pallas"),
             s((24, 300, HD)), s((24, 300, HD)), s((24, 300, HD)))


def test_ssd_compiles(one_chip):
    """mamba2-2.7b: 80 heads of P=64, state N=128, chunk 256."""
    from repro.kernels import ssd
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    bh, seq, p, n = 80, 512, 64, 128
    _compile(lambda x, la, b, c: ssd.ssd(x, la, b, c, chunk=256),
             s((bh, seq, p), jnp.bfloat16), s((bh, seq), jnp.float32),
             s((bh, seq, n), jnp.bfloat16), s((bh, seq, n), jnp.bfloat16))


@pytest.fixture(scope="module")
def llama(one_chip):
    """llama3.2-3b at published widths: model, abstract params, abstract
    bf16 arena of SLOTS x ROWS rows (all on the described chip)."""
    from repro.models import registry
    model = registry.build("llama3.2-3b").model
    params = _abstract(one_chip, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))))
    cache = _abstract(one_chip, jax.eval_shape(
        lambda: model.init_cache(SLOTS, ROWS, kv_format="bf16")))
    return model, params, cache


def test_llama_decode_step_fits_v5e(one_chip, llama, pallas_mode):
    """The engine's served decode step (greedy twin), arena donated."""
    from repro.runtime.serving import engine, sampling
    model, params, cache = llama
    vec = _spec(one_chip, (SLOTS,), jnp.int32)
    samp = _abstract(one_chip, sampling.init_slot_state(SLOTS))
    step = engine._compiled_decode_greedy(model, True)
    compiled = step.lower(params, vec, cache, vec, vec, samp).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes > 0          # the arena is donated
    assert resident < V5E_HBM_BYTES, mem
    # flash_decode reads the arena in place: no per-layer slice, pad,
    # transpose or copy of it, so no temporary near one layer's K
    k = cache["k"]
    layer_k_bytes = k.size // k.shape[0] * k.dtype.itemsize
    assert mem.temp_size_in_bytes < layer_k_bytes, mem
    relayouts = [(name, shape) for name, shape, nbytes in
                 _hlo_outputs(compiled.as_text())
                 if nbytes >= layer_k_bytes
                 and re.search(r"copy|pad|transpose|dynamic.slice", name)]
    assert not relayouts, relayouts


def test_llama_chunk_step_compiles(one_chip, llama, pallas_mode):
    """The engine's served 512-token chunk-prefill step, arena donated."""
    from repro.runtime.serving import engine
    model, params, cache = llama
    scalar = _spec(one_chip, (), jnp.int32)
    step = engine._compiled_prefill_chunk(model, True)
    compiled = step.lower(params, cache,
                          _spec(one_chip, (1, 512), jnp.int32),
                          scalar, scalar, scalar).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the qwen3-30b-a3b-24l cell: 24 layers, experts 0-15 of 128 held, 24 slots
# x 6177 rows of bf16 K/V (4 KV heads of 128, groups of 8)
MOE_SLOTS, MOE_ROWS = 24, 6177


@pytest.mark.parametrize("rows,k,n", [(24 * 8, 2048, 768),
                                      (512 * 8, 768, 2048)])
def test_expert_gmm_compiles(one_chip, rows, k, n):
    """The grouped matmul at the cell's decode and 512-token chunk row
    buffers, gate/up and down shapes, 16 held experts of 24 stacked
    layers read at a traced layer index."""
    from repro.kernels import ops
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    _compile(lambda a, w, sz, li: ops.expert_gmm(a, w, sz, layer=li,
                                                 mode="pallas"),
             s((rows, k), jnp.bfloat16), s((24, 16, k, n), jnp.bfloat16),
             s((16,), jnp.int32), s((), jnp.int32))


@pytest.fixture(scope="module")
def qwen3_moe(one_chip):
    """The benchmark's Qwen3-30B-A3B share at published widths: model,
    abstract params and the cell's abstract bf16 arena."""
    import dataclasses
    from repro.configs.base import MoEConfig
    from repro.models import registry
    full = registry.config("qwen3-moe-30b-a3b")
    cfg = dataclasses.replace(full, n_layers=24, moe=dataclasses.replace(
        full.moe, experts_held=16, first_expert=0))
    model = registry.build_model(cfg)
    params = _abstract(one_chip, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))))
    cache = _abstract(one_chip, jax.eval_shape(
        lambda: model.init_cache(MOE_SLOTS, MOE_ROWS, kv_format="bf16")))
    assert isinstance(cfg.moe, MoEConfig)
    return model, params, cache


def _no_capacity_buffer(text):
    """No (experts, capacity, d) dispatch buffer, and no one layer's
    expert weights copied out of the (24, 16, ...) stack: the expert layer
    reads the stack in place, so no op outputs an array of (1 x) 16 x
    ... x 2048 or (1 x) 16 x 2048 x ...."""
    bufs = [(name, dims) for name, dims, _ in _hlo_outputs(text)
            if re.fullmatch(r"(1,)?16,(\d+,2048|2048,\d+)", dims)]
    assert not bufs, bufs


def test_qwen3_moe_decode_step_fits_v5e(one_chip, qwen3_moe, pallas_mode):
    """The served MoE decode step (greedy twin), arena donated: the expert
    layer is ``expert_gmm`` beside ``flash_decode``, the step returns its
    routing counters, and it fits one chip."""
    from repro.runtime.serving import engine, sampling
    model, params, cache = qwen3_moe
    vec = _spec(one_chip, (MOE_SLOTS,), jnp.int32)
    samp = _abstract(one_chip, sampling.init_slot_state(MOE_SLOTS))
    step = engine._compiled_decode_greedy(model, True)
    lowered = step.lower(params, vec, cache, vec, vec, samp)
    assert lowered.out_info[-1].shape == (2,)        # the counters
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "%expert_gmm" in text and "%flash_decode" in text
    _no_capacity_buffer(text)
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes > 0
    assert resident < V5E_HBM_BYTES, mem


def test_qwen3_moe_chunk_step_compiles(one_chip, qwen3_moe, pallas_mode):
    """The served 512-token MoE chunk step, arena donated."""
    from repro.runtime.serving import engine
    model, params, cache = qwen3_moe
    scalar = _spec(one_chip, (), jnp.int32)
    step = engine._compiled_prefill_chunk(model, True)
    compiled = step.lower(params, cache,
                          _spec(one_chip, (1, 512), jnp.int32),
                          scalar, scalar, scalar).compile()
    text = compiled.as_text()
    assert "%expert_gmm" in text
    _no_capacity_buffer(text)
    # the chunk's rows are scattered into the 4-KV-head arena in place:
    # no copy of an arena leaf, and the step fits one chip
    leaf = cache["k"].size * cache["k"].dtype.itemsize
    copies = [(name, dims) for name, dims, nbytes in _hlo_outputs(text)
              if nbytes >= leaf and re.search(r"copy|transpose", name)]
    assert not copies, copies
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < leaf // 4, mem
    assert (mem.argument_size_in_bytes - mem.alias_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            < V5E_HBM_BYTES), mem
