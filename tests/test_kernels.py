"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracles.

Each kernel is swept over shapes (aligned and ragged tails) and dtypes and
asserted allclose against ``kernels/ref.py``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(42)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# matmul (fmatmul analogue)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (8, 16, 8), (128, 128, 128), (96, 130, 70), (257, 64, 33), (1, 512, 1),
])
def test_matmul_vs_ref(shape, dtype):
    m, k, n = shape
    a = _rand(KEY, (m, k), dtype)
    b = _rand(jax.random.PRNGKey(7), (k, n), dtype)
    out = ops.matmul(a, b, mode="interpret")
    want = ref.matmul(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# dotp (chained vmul+vredsum, C4+C5)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [8, 100, 1024, 4097])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dotp_vs_ref(n, dtype):
    a = _rand(KEY, (n,), dtype)
    b = _rand(jax.random.PRNGKey(3), (n,), dtype)
    out = ops.dotp(a, b, mode="interpret")
    want = ref.dotp(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4)


# ---------------------------------------------------------------------------
# conv2d (fconv2d 7x7 analogue)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hw,cin,cout,k", [
    ((16, 16), 3, 8, 7), ((32, 20), 4, 4, 3), ((9, 9), 1, 2, 7),
])
def test_conv2d_vs_ref(hw, cin, cout, k):
    h, w = hw
    x = _rand(KEY, (2, h, w, cin), jnp.float32)
    wgt = _rand(jax.random.PRNGKey(5), (k, k, cin, cout), jnp.float32)
    out = ops.conv2d(x, wgt, mode="interpret")
    want = ref.conv2d(x, wgt)
    np.testing.assert_allclose(out, want, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# attention (flash kernel + blockwise ref)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["interpret", "ref"])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 16),
                                           (False, None)])
@pytest.mark.parametrize("sq,sk", [(64, 64), (33, 33), (1, 128)])
def test_attention_vs_ref(mode, causal, window, sq, sk):
    if sq != sk and causal is False:
        pytest.skip("cross-attention covered by (False, None) square")
    d = 16
    q = _rand(KEY, (3, sq, d), jnp.float32)
    k = _rand(jax.random.PRNGKey(1), (3, sk, d), jnp.float32)
    v = _rand(jax.random.PRNGKey(2), (3, sk, d), jnp.float32)
    out = ops.attention(q, k, v, causal=causal, window=window, mode=mode,
                        bq=32, bk=32)
    want = jax.vmap(functools.partial(ref.attention, causal=causal,
                                      window=window))(q, k, v)
    np.testing.assert_allclose(out, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sq,sk,bq,bk", [(40, 40, 32, 64), (24, 40, 16, 16)])
def test_attention_ragged_blocks_interpret(causal, sq, sk, bq, bk):
    """Ragged query and key lengths are zero-padded to whole blocks on the
    kernel path; padded keys are predicated off and queries stay
    right-aligned on the *real* lengths, whatever the two block sizes."""
    d = 16
    q = _rand(KEY, (2, sq, d), jnp.float32)
    k = _rand(jax.random.PRNGKey(1), (2, sk, d), jnp.float32)
    v = _rand(jax.random.PRNGKey(2), (2, sk, d), jnp.float32)
    out = ops.attention(q, k, v, causal=causal, mode="interpret",
                        bq=bq, bk=bk)
    want = jax.vmap(functools.partial(ref.attention, causal=causal))(q, k, v)
    np.testing.assert_allclose(out, want, rtol=2e-3, atol=2e-3)


def test_attention_4d_matches_3d():
    q = _rand(KEY, (2, 4, 32, 16), jnp.float32)
    out4 = ops.attention(q, q, q, causal=True, mode="ref")
    out3 = ops.attention(q.reshape(8, 32, 16), q.reshape(8, 32, 16),
                         q.reshape(8, 32, 16), causal=True, mode="ref")
    np.testing.assert_allclose(out4.reshape(8, 32, 16), out3, rtol=1e-6)


def test_attention_decode_right_alignment():
    """Sq=1 decode: the single query sits at the *last* KV position."""
    d, sk = 8, 40
    q = _rand(KEY, (1, 1, d), jnp.float32)
    k = _rand(jax.random.PRNGKey(1), (1, sk, d), jnp.float32)
    v = _rand(jax.random.PRNGKey(2), (1, sk, d), jnp.float32)
    out = ops.attention(q, k, v, causal=True, mode="ref")
    want = ref.attention(q[0], k[0], v[0], causal=True)
    np.testing.assert_allclose(out[0], want, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# SSD (Mamba2 chunked scan)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["interpret", "ref"])
@pytest.mark.parametrize("s,chunk", [(64, 16), (64, 64), (48, 16), (40, 16)])
def test_ssd_vs_ref(mode, s, chunk):
    bh, p, n = 3, 16, 8
    x = _rand(KEY, (bh, s, p), jnp.float32)
    la = -jnp.abs(_rand(jax.random.PRNGKey(1), (bh, s), jnp.float32)) * 0.1
    B = _rand(jax.random.PRNGKey(2), (bh, s, n), jnp.float32)
    C = _rand(jax.random.PRNGKey(3), (bh, s, n), jnp.float32)
    y, st = ops.ssd(x, la, B, C, chunk=chunk, mode=mode)
    yr, str_ = jax.vmap(ref.ssd)(x, la, B, C)
    np.testing.assert_allclose(y, yr, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(st, str_, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("mode", ["interpret", "ref"])
def test_ssd_chunked_state_chaining(mode):
    """Chunked scan carry-in/carry-out == contiguous run (C7 strip-mining).
    ``initial_state`` is a kernel operand on every path (the Pallas kernel
    seeds its VMEM carry from it), so serving's chunked prefill — which
    threads the SSD state across bucket-sized prompt chunks — does not
    fall back to the jnp path on TPU."""
    bh, s, p, n = 2, 64, 8, 4
    x = _rand(KEY, (bh, s, p), jnp.float32)
    la = -jnp.abs(_rand(jax.random.PRNGKey(1), (bh, s), jnp.float32)) * 0.2
    B = _rand(jax.random.PRNGKey(2), (bh, s, n), jnp.float32)
    C = _rand(jax.random.PRNGKey(3), (bh, s, n), jnp.float32)
    y_full, st_full = ops.ssd(x, la, B, C, chunk=16, mode=mode)
    h = s // 2
    y1, st1 = ops.ssd(x[:, :h], la[:, :h], B[:, :h], C[:, :h],
                      chunk=16, mode=mode)
    y2, st2 = ops.ssd(x[:, h:], la[:, h:], B[:, h:], C[:, h:],
                      chunk=16, mode=mode, initial_state=st1)
    np.testing.assert_allclose(
        jnp.concatenate([y1, y2], axis=1), y_full, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(st2, st_full, rtol=2e-3, atol=2e-3)


def test_ssd_decode_step_matches_scan():
    bh, s, p, n = 2, 8, 4, 4
    x = _rand(KEY, (bh, s, p), jnp.float32)
    la = -jnp.abs(_rand(jax.random.PRNGKey(1), (bh, s), jnp.float32)) * 0.2
    B = _rand(jax.random.PRNGKey(2), (bh, s, n), jnp.float32)
    C = _rand(jax.random.PRNGKey(3), (bh, s, n), jnp.float32)
    y_scan, _ = jax.vmap(ref.ssd)(x, la, B, C)
    state = jnp.zeros((bh, n, p), jnp.float32)
    outs = []
    for t in range(s):
        y_t, state = ops.ssd_decode_step(x[:, t], la[:, t], B[:, t],
                                         C[:, t], state)
        outs.append(y_t)
    np.testing.assert_allclose(jnp.stack(outs, 1), y_scan,
                               rtol=2e-3, atol=2e-3)
