"""Chained vfmul→vfredsum dot product (paper §V.e + §VI.A.b — C4+C5).

Ara's dot-product benchmark (Table II) chains an elementwise multiply into
the 3-step reduction so total cycles track the element count.  The TPU vreg
is (8 sublanes × 128 lanes); this kernel maps the paper's steps onto that
geometry:

  step 0 (chaining)   — each grid step multiplies a VMEM strip and *adds it
                        into* an (8,128) f32 accumulator: the multiply chains
                        into the reduction, no intermediate is materialised;
  step 1 (intra-lane) — the strided accumulation above *is* the intra-lane
                        reduction: lane j of the vreg accumulates elements
                        j mod 128, slot-major, exactly the VRF mapping;
  step 2 (inter-lane) — on the last grid step, a log2(128)-shaped fold over
                        the 128 vreg lanes (jnp.sum lowers to the tree);
  step 3 (SIMD fold)  — final fold over the 8 sublanes.

The (8,128)-strip layout means the kernel reduces in *exactly* the paper's
partial-sum order, which the property tests exploit (bitwise match against
``core.reduction.lane_tree_reduce`` with lanes=128, eew=8 modulo the f32 vs
f64 question — see tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8
LANES = 128
DEFAULT_STRIP = 16 * SUBLANES * LANES   # elements per grid step (16 vregs)


def _dotp_kernel(a_ref, b_ref, o_ref, acc_ref, *, nsteps: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    prod = (a_ref[...].astype(jnp.float32) * b_ref[...].astype(jnp.float32))
    # strip is (strip_elems,) -> (slots, 8, 128); accumulate slot-major
    acc_ref[...] += prod.reshape(-1, SUBLANES, LANES).sum(axis=0)

    @pl.when(i == nsteps - 1)
    def _reduce():
        word = acc_ref[...]
        o_ref[0, 0] = jnp.sum(word)        # inter-lane tree + SIMD fold


def dotp(a: jax.Array, b: jax.Array, *, strip: int = DEFAULT_STRIP,
         interpret: bool = False) -> jax.Array:
    """f32 dot product of equal-length 1-D vectors; len % strip == 0."""
    (n,) = a.shape
    assert a.shape == b.shape
    if n % strip or strip % (SUBLANES * LANES):
        raise ValueError(f"length {n} must divide strip {strip} "
                         f"(multiple of {SUBLANES * LANES})")
    nsteps = n // strip
    out = pl.pallas_call(
        functools.partial(_dotp_kernel, nsteps=nsteps),
        grid=(nsteps,),
        in_specs=[pl.BlockSpec((strip,), lambda i: (i,)),
                  pl.BlockSpec((strip,), lambda i: (i,))],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="dotp",
        interpret=interpret,
    )(a, b)
    return out[0, 0]
