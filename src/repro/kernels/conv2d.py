"""fconv2d — 7×7 valid convolution Pallas kernel (paper §VI.A).

Ara's second flagship kernel (fconv2d, 7×7×3).  TPU adaptation: instead of
the lane-sliced stencil of the paper, each grid step computes one output
tile as 49 accumulated (bh·bw, Cin) × (Cin, Cout) MXU matmuls — a direct
(shift ∘ matmul) stencil that keeps the accumulator in VMEM (chaining) and
feeds the MXU dense operands.

VMEM policy (DESIGN.md §6): the whole padded input image of one batch
element is staged in VMEM and windows are sliced in-kernel (7×7 halos
overlap, which BlockSpec tiling cannot express).  That bounds the supported
image size to VMEM (e.g. 256×256×16 f32 ≈ 4 MiB) — matching the paper's
workload class (small images, few channels).  Larger images strip-mine over
rows at the ``ops.py`` level.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _conv_kernel(x_ref, w_ref, o_ref, acc_ref, *, kh: int, kw: int,
                 bh: int, bw: int):
    i = pl.program_id(1)   # output row-tile
    j = pl.program_id(2)   # output col-tile
    acc_ref[...] = jnp.zeros_like(acc_ref)
    x = x_ref[0]                                   # (Hp, Wp, Cin)
    cin = x.shape[-1]
    for ky in range(kh):
        for kx in range(kw):
            window = jax.lax.dynamic_slice(
                x, (i * bh + ky, j * bw + kx, 0), (bh, bw, cin))
            lhs = window.reshape(bh * bw, cin)
            rhs = w_ref[ky, kx]                     # (Cin, Cout_blk)
            acc_ref[...] += jnp.dot(lhs, rhs,
                                    preferred_element_type=jnp.float32)
    o_ref[0] = acc_ref[...].reshape(bh, bw, -1).astype(o_ref.dtype)


def conv2d(x: jax.Array, w: jax.Array, *, bh: int = 8, bw: int = 128,
           bco: int | None = None, interpret: bool = False) -> jax.Array:
    """Valid conv: x (N,H,W,Cin) × w (KH,KW,Cin,Cout) -> (N,Ho,Wo,Cout).

    Requires Ho % bh == Wo % bw == Cout % bco == 0 (ops.py pads otherwise).
    """
    n, h, wd, cin = x.shape
    kh, kw, cin2, cout = w.shape
    assert cin == cin2
    ho, wo = h - kh + 1, wd - kw + 1
    bco = bco or cout
    if ho % bh or wo % bw or cout % bco:
        raise ValueError(f"unaligned output {ho}x{wo}x{cout} for blocks "
                         f"({bh},{bw},{bco}); use ops.conv2d for padding")
    return pl.pallas_call(
        functools.partial(_conv_kernel, kh=kh, kw=kw, bh=bh, bw=bw),
        grid=(n, ho // bh, wo // bw, cout // bco),
        in_specs=[
            # full padded image of one batch element resident in VMEM
            pl.BlockSpec((1, h, wd, cin), lambda b, i, j, c: (b, 0, 0, 0)),
            pl.BlockSpec((kh, kw, cin, bco), lambda b, i, j, c: (0, 0, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, bh, bw, bco),
                               lambda b, i, j, c: (b, i, j, c)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, cout), x.dtype),
        scratch_shapes=[pltpu.VMEM((bh * bw, bco), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel")),
        name="conv2d",
        interpret=interpret,
    )(x, w)
