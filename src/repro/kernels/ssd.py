"""Mamba2 SSD (state-space duality) chunk kernel — strip-mining with a
recurrent carry (C7 + C4).

The SSD algorithm *is* Ara's execution model applied to a recurrence:

  * the sequence is strip-mined into chunks of Q tokens (the VLEN loop),
  * intra-chunk work is dense, data-local matmuls — (C Bᵀ ⊙ L) X — i.e. the
    intra-lane step that keeps the MXU (VMFPU) at full utilisation,
  * the inter-chunk SSM state hand-off is the slide-unit step: a small
    (N × P) carry crosses strip boundaries once per chunk,
  * the final output mix (Y_intra + C·state) is the SIMD-fold analogue.

Grid = (batch·heads, S/Q), sequential inner axis; the carry state lives in a
VMEM scratch that persists across grid steps of the same (batch·head) row.
The log-decay travels as a lane-dense (BH, 1, S) row, so its (1, Q) strip
block meets the TPU's last-two-dims tiling rule.

Semantics (dt pre-folded into x and the log-decay):
  state_j = exp(la_j)·state_{j-1} + B_j ⊗ x_j ;  y_j = C_j · state_j
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(x_ref, la_ref, b_ref, c_ref, st0_ref, y_ref, st_out_ref,
                state_ref, *, nchunks: int):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        # seed the carry from the caller's initial state (zeros at sequence
        # start; the previous chunk's carry-out under serving's stripmined
        # prefill, where the recurrence is threaded across chunk calls)
        state_ref[...] = st0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)       # (Q, P)
    la = la_ref[0].astype(jnp.float32)     # (1, Q)
    B = b_ref[0].astype(jnp.float32)       # (Q, N)
    C = c_ref[0].astype(jnp.float32)       # (Q, N)
    q = x.shape[0]

    # inclusive within-chunk decay (Mosaic has no cumsum): as a column, a
    # masked lane reduction over the lower triangle; as a row, the same
    # triangle applied by a full-f32-precision matmul
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lower = ii >= jj
    cum_col = jnp.sum(jnp.where(lower, la, 0.0), axis=1,
                      keepdims=True)                      # (Q, 1)
    cum_row = jnp.dot(la, jnp.where(ii <= jj, 1.0, 0.0),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)  # (1, Q)
    total = jnp.sum(la, axis=1, keepdims=True)            # (1, 1)

    # intra-chunk (dense, MXU): scores[i,j] = (C_i·B_j)·exp(cum_i - cum_j), j<=i
    seg = jnp.where(lower, cum_col - cum_row, NEG_INF)
    scores = jnp.dot(C, B.T, preferred_element_type=jnp.float32) * jnp.exp(seg)
    y = jnp.dot(scores, x, preferred_element_type=jnp.float32)

    # carry-in from previous chunks (slide step)
    state = state_ref[...]                  # (N, P)
    y += jnp.dot(C * jnp.exp(cum_col), state,
                 preferred_element_type=jnp.float32)

    # state update for the next chunk
    weights = jnp.exp(total - cum_col) * B  # (Q, N)
    state_ref[...] = jnp.exp(total) * state + jnp.dot(
        weights.T, x, preferred_element_type=jnp.float32)

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(c_idx == nchunks - 1)
    def _flush():
        st_out_ref[0] = state_ref[...]


def ssd(x: jax.Array, log_a: jax.Array, B: jax.Array, C: jax.Array, *,
        chunk: int = 256, initial_state: jax.Array | None = None,
        interpret: bool = False):
    """x: (BH, S, P), log_a: (BH, S), B/C: (BH, S, N) -> (y, final_state).

    y: (BH, S, P); final_state: (BH, N, P) f32.  Requires S % chunk == 0.
    ``initial_state`` (BH, N, P) seeds the recurrence carry (None = zeros)
    — the inter-*call* half of the slide-unit hand-off, used by serving's
    chunked prefill to thread the SSD state across bucket-sized prompt
    chunks without re-running the prefix.
    """
    bh, s, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"S={s} not a multiple of chunk={chunk}")
    nchunks = s // chunk
    st0 = (jnp.zeros((bh, n, p), jnp.float32) if initial_state is None
           else initial_state.astype(jnp.float32))
    y, st = pl.pallas_call(
        functools.partial(_ssd_kernel, nchunks=nchunks),
        grid=(bh, nchunks),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, n, p), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, n, p), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ssd",
        interpret=interpret,
    )(x, log_a[:, None, :], B, C, st0)
    return y, st
