"""Dense GQA decoder (Qwen3 family): the benchmark's weights and its plain
reference, in ``jax.numpy`` and float32.

The reference follows the published architecture (Qwen3 ``config.json``
and modelling code): token embedding; per layer RMSNorm, q/k/v
projections, per-head RMSNorm on q and k (``qk_norm``), rotary embedding
over split halves with ``rope_theta``, causal grouped-query attention
scaled by ``head_dim ** -0.5``, output projection, residual; RMSNorm and a
SiLU-gated MLP, residual; a final RMSNorm and an untied output head.  No
departure from that description.

It imports nothing of the program under test.  It reads the weights by
the names of the program's parameter tree (``embed``, ``layers/attn/wq``,
...), which the benchmark fills itself from the seed (:func:`make_params`).

``mode="f32"`` is the reference: every product in float32 at
``Precision.HIGHEST``.  ``mode="fp8"`` is the control: the same forward
with every matrix-product operand rounded to float8 e4m3 (per-row scales
for activations, per-column scales for weights), the precision step below
the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
BLOCK = 512            # query block of the reference attention


def program_config(cfg: dict):
    """The program's ``ArchConfig`` for this configuration file."""
    from repro.configs.base import ArchConfig
    dtype = cfg["torch_dtype"]
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        act={"silu": "silu_gated"}[cfg["hidden_act"]],
        qk_norm=cfg["model_type"] == "qwen3",
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=dtype, act_dtype=dtype,
        max_seq=cfg["max_position_embeddings"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make_params(cfg: dict, shapes, seed: int):
    """Weights in the program's tree layout (``shapes``: its
    ``jax.eval_shape`` of ``init``), made on the device in one jitted call:
    norm scales 1, every matrix N(0, initializer_range**2) as the published
    configuration initialises it, in the leaf's own dtype."""
    std = float(cfg["initializer_range"])
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = str(path[-1].key)
            if name == "scale":
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, leaf.shape, jnp.float32)
                            * std).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------------------
# reference forward
# ---------------------------------------------------------------------------

def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, mode):
    """a (..., k) @ b (k, n) in float32, operands rounded for the control."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.dot(a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (S, H, hd); rotary embedding over split halves at 0..S-1."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = np.arange(s, dtype=np.float32)[:, None] * freqs     # (S, half)
    cos = jnp.asarray(np.cos(ang))[:, None, :]
    sin = jnp.asarray(np.sin(ang))[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, mode):
    """Causal GQA. q: (S, H, hd); k, v: (S, KVH, hd) -> (S, H*hd)."""
    s, h, hd = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    if mode == "fp8":
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    scale = hd ** -0.5
    cols = jnp.arange(s)

    def block(b):
        qb = jax.lax.dynamic_slice_in_dim(q, b * BLOCK, BLOCK, 0)
        rows = b * BLOCK + jnp.arange(BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        sc = jnp.where(cols[None, None, :] <= rows[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if mode == "fp8":
            p = _q8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(s // BLOCK))
    return out.reshape(s, h * hd)


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def _layer(lp, x, *, cfg, mode):
    nh, nkv, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    eps = cfg["eps"]
    s = x.shape[0]
    a = lp["attn"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    q = _mm(h, a["wq"], mode).reshape(s, nh, hd)
    k = _mm(h, a["wk"], mode).reshape(s, nkv, hd)
    v = _mm(h, a["wv"], mode).reshape(s, nkv, hd)
    if cfg["qk_norm"]:
        q = _rms(q, a["q_norm"]["scale"], eps)
        k = _rms(k, a["k_norm"]["scale"], eps)
    q, k = _rope(q, cfg["theta"]), _rope(k, cfg["theta"])
    x = x + _mm(_attention(q, k, v, mode), a["wo"], mode)
    m = lp["mlp"]
    h = _rms(x, lp["ln2"]["scale"], eps)
    gate = _mm(h, m["w_gate"], mode)
    x = x + _mm(jax.nn.silu(gate) * _mm(h, m["w_up"], mode), m["w_down"],
                mode)
    return x


@jax.jit
def _take(layers, i):
    return jax.tree.map(lambda a: a[i], layers)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _logits(params, x, rows, *, eps, mode):
    head = (params["lm_head"] if "lm_head" in params
            else params["embed"].T)
    h = _rms(x[rows], params["final_norm"]["scale"], eps)
    return _mm(h, head, mode)


class _Frozen(dict):
    """A hashable dict, so the layer sizes can be a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _static(cfg: dict) -> _Frozen:
    return _Frozen(heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg["head_dim"], eps=cfg["rms_norm_eps"],
                   qk_norm=cfg["model_type"] == "qwen3",
                   theta=float(cfg["rope_theta"]))


def logits(cfg: dict, params, tokens: np.ndarray, rows: np.ndarray,
           mode: str = "f32") -> jax.Array:
    """Teacher-forced logits (len(rows), V) float32 at positions ``rows``
    of ``tokens`` (one sequence).  The sequence is padded to a whole number
    of attention blocks, rounded up to a power of two so that few shapes
    compile (causal, so the padding changes nothing before it), and run
    layer by layer, so at most one layer is widened to float32."""
    static = _static(cfg)
    n = tokens.shape[0]
    padded = np.zeros(BLOCK * 2 ** int(np.ceil(np.log2(-(-n // BLOCK)))),
                      np.int32)
    padded[:n] = tokens
    x = params["embed"][jnp.asarray(padded)].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(_take(params["layers"], i), x, cfg=static, mode=mode)
    return _logits(params, x, jnp.asarray(rows, jnp.int32),
                   eps=cfg["rms_norm_eps"], mode=mode)
