"""fmatmul — MXU-tiled GEMM Pallas kernel (the VMFPU analogue, paper §VI.A).

TPU adaptation of Ara's flagship kernel.  The paper's lane keeps an operand
queue + FPU busy every cycle from its local VRF chunk; here each grid step
keeps the MXU busy from VMEM-resident blocks:

  * grid = (M/bm, N/bn, K/bk), innermost axis walks the contraction so the
    f32 accumulator block stays resident in VMEM (the "chaining keeps
    operands in the operand queues" property),
  * block shapes are multiples of the 128×128 MXU tile; defaults
    (256, 512, 256) keep the working set (a + b + acc ≈ 0.9 MiB bf16/f32)
    well inside VMEM with double-buffering headroom (the VRF-sizing rule,
    DESIGN.md §6),
  * accumulation is always f32 regardless of input dtype (the paper's FPU is
    a true FMA; bf16 inputs hit the MXU's native path).

Non-aligned shapes are handled by the wrapper in ``ops.py`` (pad + slice —
the tail-predication C3 path), keeping the kernel itself branch-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256
DEFAULT_BK = 512
DEFAULT_BN = 256


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(a: jax.Array, b: jax.Array, *, bm: int = DEFAULT_BM,
           bk: int = DEFAULT_BK, bn: int = DEFAULT_BN,
           out_dtype=None, interpret: bool = False) -> jax.Array:
    """C[M,N] = A[M,K] @ B[K,N]; requires M%bm == K%bk == N%bn == 0."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if m % bm or k % bk or n % bn:
        raise ValueError(f"unaligned shapes {a.shape}x{b.shape} for blocks "
                         f"({bm},{bk},{bn}); use ops.matmul for padding")
    out_dtype = out_dtype or a.dtype
    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=k // bk),
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="matmul",
        interpret=interpret,
    )(a, b)
