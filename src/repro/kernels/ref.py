"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the *semantic definition* the kernels are tested against
(tests/test_kernels_*.py sweep shapes & dtypes and assert_allclose).  They are
deliberately naive — full materialisation, no blocking — so correctness is
obvious by inspection.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """C = A @ B with f32 accumulation (fmatmul oracle)."""
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def dotp(a: jax.Array, b: jax.Array) -> jax.Array:
    """Chained vfmul + vfredsum oracle: f32 scalar dot product."""
    return jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32))


def conv2d(x: jax.Array, w: jax.Array) -> jax.Array:
    """2-D valid convolution, NHWC × HWIO -> NHWC (fconv2d oracle)."""
    return lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int | None = None,
              scale: float | None = None) -> jax.Array:
    """Full-softmax attention oracle.

    q: (Sq, D), k/v: (Sk, D).  ``window`` is a sliding-attention width
    (causal band), counted inclusive of the current position.  For decode,
    Sq == 1 and positions are right-aligned with the KV sequence.
    """
    sq, d = q.shape
    sk = k.shape[0]
    scale = scale if scale is not None else d ** -0.5
    logits = (q.astype(jnp.float32) @ k.astype(jnp.float32).T) * scale
    qpos = jnp.arange(sq)[:, None] + (sk - sq)   # right-aligned
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return (probs @ v.astype(jnp.float32)).astype(q.dtype)


def ssd(x: jax.Array, log_a: jax.Array, B: jax.Array, C: jax.Array,
        state: jax.Array | None = None):
    """Mamba2 SSD (state-space dual) oracle: naive per-step recurrence.

    x:      (S, P)   per-head inputs (dt already folded in)
    log_a:  (S,)     per-step log decay (<= 0)
    B, C:   (S, N)   input/output projections
    state:  (N, P)   carry-in SSM state (zeros if None)

    Returns (y: (S, P), final_state: (N, P)); all math in f32.
    """
    s, p = x.shape
    n = B.shape[-1]
    x32, B32, C32 = (t.astype(jnp.float32) for t in (x, B, C))
    la = log_a.astype(jnp.float32)
    st0 = jnp.zeros((n, p), jnp.float32) if state is None \
        else state.astype(jnp.float32)

    def step(st, inp):
        xt, lat, bt, ct = inp
        st = jnp.exp(lat) * st + bt[:, None] * xt[None, :]
        return st, ct @ st

    final, y = lax.scan(step, st0, (x32, la, B32, C32))
    return y.astype(x.dtype), final


def expert_gmm(lhs: jax.Array, rhs: jax.Array,
               sizes: jax.Array) -> jax.Array:
    """Grouped matmul oracle: ``lhs`` (M, K) rows sorted by group, group
    ``g`` owning ``sizes[g]`` consecutive rows; row r times ``rhs[g]`` of
    its group (rhs (G, K, N)), f32; rows past ``sizes.sum()`` are zero."""
    ends = jnp.cumsum(sizes)
    row = jnp.arange(lhs.shape[0])
    group = jnp.sum(row[:, None] >= ends[None, :], axis=1)    # G: no group
    a = lhs.astype(jnp.float32)

    def one(g, out):
        y = jnp.dot(a, rhs[g].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        return jnp.where((group == g)[:, None], y, out)

    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    return lax.fori_loop(0, rhs.shape[0], one, out)
