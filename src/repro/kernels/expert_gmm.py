"""Grouped matmul over the experts held here: rows sorted by expert, each
group of rows times its own expert's weight matrix (C3+C5).

A dropless expert layer sorts the (token, choice) rows that land on the
experts this chip holds by expert, so that expert ``g``'s rows are
``[offsets[g], offsets[g + 1])`` of one ``(M, K)`` buffer.  ``M`` is the
worst case (every choice of every token held here); the rows past
``offsets[-1]`` hold no defined value and nothing reads them.

The grid is ``(column tiles, work items)``.  A work item is one row tile
of ``tm`` rows visited for one expert: a tile that two experts' rows share
is visited once for each, consecutively, and each visit stores only its
own expert's rows.  The work list (:func:`schedule`) is computed on the
device from the group sizes and scalar-prefetched; its length is the
grid's second extent, so the kernel runs exactly the work items the
routing produced — no row tile without rows, and no expert without rows,
is ever visited.  The weights may be stacked over layers, ``(L, G, K, N)``
with the layer scalar-prefetched, so that a caller scanning layers hands
over the whole stack rather than a per-layer slice (a Pallas call cannot
fuse the slice, so XLA would copy every expert's weights first).  The
weight block an item reads is its layer's and expert's ``(K, tn)``
columns, so an expert's weights are fetched once per column
tile (consecutive items of one expert keep the same block, which the
pipeline does not fetch again) and an expert with no rows fetches none.

Each item is one ``(tm, K) @ (K, tn)`` dot accumulated in float32 and
rounded to the output dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of one weight block in VMEM (it is double-buffered)
RHS_BLOCK_BYTES = 4 << 20


def row_tile(m: int) -> int:
    """Row tile for an ``M``-row buffer: 16 rows (one bf16 sublane tile)
    for decode-sized buffers, growing with ``M`` up to 128."""
    tm = 16
    while tm < 128 and tm * 16 < m:
        tm *= 2
    return tm


def col_tile(k: int, n: int, itemsize: int) -> int:
    """Output columns per weight block: all ``n`` when a ``(K, n)`` block
    fits :data:`RHS_BLOCK_BYTES`, else the widest multiple of 128 that
    divides ``n`` and fits."""
    if k * n * itemsize <= RHS_BLOCK_BYTES or n % 128:
        return n
    tn = n
    while tn > 128 and (n % tn or k * tn * itemsize > RHS_BLOCK_BYTES):
        tn -= 128
    return tn


def schedule(sizes: jax.Array, m: int, tm: int):
    """The grid's work list for group sizes ``sizes`` (G,) over ``m`` rows.

    Returns ``(offsets (G+1,), groups (W,), tiles (W,), n_work ())`` with
    ``W = m // tm + G - 1`` (the most items any routing can need): item
    ``w < n_work`` is row tile ``tiles[w]`` for expert ``groups[w]``, in
    row order, so the visits of one output tile are consecutive.  Items
    from ``n_work`` on repeat the last real one (never run)."""
    g = sizes.shape[0]
    cap = m // tm + g - 1
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts // tm
    per_group = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    n_work = per_group.sum()
    w = jnp.arange(cap, dtype=jnp.int32)
    groups = jnp.repeat(jnp.arange(g, dtype=jnp.int32), per_group,
                        total_repeat_length=cap)
    lead = jnp.cumsum(per_group) - per_group      # first item of each group
    tiles = first[groups] + w - lead[groups]
    last = jnp.maximum(n_work - 1, 0)
    groups = jnp.where(w < n_work, groups, groups[last])
    tiles = jnp.where(w < n_work, tiles, tiles[last])
    return offsets, groups, tiles, n_work


def _lhs_block(n, w, layer, offsets, groups, tiles):
    return tiles[w], 0


def _rhs_block(n, w, layer, offsets, groups, tiles):
    return layer[0], groups[w], 0, n


def _out_block(n, w, layer, offsets, groups, tiles):
    return tiles[w], n


def _kernel(layer_ref, offsets_ref, groups_ref, tiles_ref, lhs_ref, rhs_ref,
            out_ref, *, tm: int):
    w = pl.program_id(1)
    g = groups_ref[w]
    row = (tiles_ref[w] * tm
           + lax.broadcasted_iota(jnp.int32, out_ref.shape, 0))
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                  preferred_element_type=jnp.float32)
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def expert_gmm(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array,
               layer: jax.Array, *, tm: int, tn: int,
               interpret: bool = False) -> jax.Array:
    """``lhs`` (M, K) rows sorted by expert, ``rhs`` (L, G, K, N) the held
    experts' weights of every layer, ``sizes`` (G,) int32 rows per expert,
    ``layer`` () int32 the layer read -> (M, N) in ``lhs``'s dtype; rows
    past ``sizes.sum()`` are undefined.  ``M`` must be a multiple of
    ``tm`` and ``N`` of ``tn``."""
    m, k = lhs.shape
    _, g, _, n = rhs.shape
    offsets, groups, tiles, n_work = schedule(sizes, m, tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, n_work),
        in_specs=[pl.BlockSpec((tm, k), _lhs_block),
                  pl.BlockSpec((None, None, k, tn), _rhs_block)],
        out_specs=pl.BlockSpec((tm, tn), _out_block))
    nbytes = (lhs.dtype.itemsize * m * k + rhs.dtype.itemsize * g * k * n
              + lhs.dtype.itemsize * m * n)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(flops=2 * m * k * n,
                                      bytes_accessed=nbytes,
                                      transcendentals=0),
        name="expert_gmm",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), offsets, groups, tiles,
      lhs, rhs)
