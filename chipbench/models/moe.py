"""Sparse-MoE GQA decoder (Qwen3-MoE family): the benchmark's weights and
its plain reference, in ``jax.numpy`` and float32.

The reference follows the published architecture (Qwen3-MoE
``config.json`` and HF ``Qwen3MoeSparseMoeBlock``): the dense reference's
attention half (``dense.py``: RMSNorm, q/k/v projections, per-head RMSNorm
on q and k, rotary embedding, causal grouped-query attention, output
projection, residual), then RMSNorm and the expert block — router logits
``x @ W_r`` without bias, softmax over all the router's experts in
float32, the top ``num_experts_per_tok`` with their weights divided by
their sum (``norm_topk_prob``), ``y = sum_i w_i * W_down,i(silu(W_gate,i
x) * W_up,i x)`` — and a residual; a final RMSNorm and an untied head.

One departure, the share this chip holds: only the ``num_experts``
experts from ``first_expert`` exist here (the router still has
``router_experts`` outputs and the top-k is taken over all of them), and a
choice that falls on an expert held elsewhere adds nothing.

It imports nothing of the program under test and reads the weights by the
names of the program's parameter tree (``layers/moe/router``,
``layers/moe/experts/w_gate``, ...), which the benchmark fills from the
seed with ``dense.make_params``.  ``mode="f32"`` is the reference, every
product in float32 at ``Precision.HIGHEST``; ``mode="fp8"`` the control,
every matrix-product operand (the router's included) rounded to float8
e4m3 as in ``dense.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec

dense = spec.model_module("dense")
BLOCK = dense.BLOCK
make_params = dense.make_params


def program_config(cfg: dict):
    """The program's ``ArchConfig`` for this configuration file."""
    from repro.configs.base import ArchConfig, MoEConfig
    dtype = cfg["torch_dtype"]
    return ArchConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
        act={"silu": "silu_gated"}[cfg["hidden_act"]],
        qk_norm=cfg["model_type"] == "qwen3_moe",
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        moe=MoEConfig(n_experts=cfg["router_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      norm_topk_prob=cfg["norm_topk_prob"],
                      router_aux_weight=cfg["router_aux_loss_coef"],
                      experts_held=cfg["num_experts"],
                      first_expert=cfg["first_expert"]),
        param_dtype=dtype, act_dtype=dtype,
        max_seq=cfg["max_position_embeddings"])


def _experts(m, h, cfg, mode):
    """The expert block over the held experts. h: (S, d) f32 -> (y (S, d),
    the top-k expert ids (S, k))."""
    probs = jax.nn.softmax(dense._mm(h, m["router"], mode), axis=-1)
    w, ids = jax.lax.top_k(probs, cfg["top_k"])
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    held = m["experts"]["w_gate"].shape[0]
    # each token's weight on each held expert (0 where it did not choose it)
    coef = (w[:, :, None] * (ids[:, :, None] == cfg["first"]
                             + jnp.arange(held)[None, None, :])).sum(1)

    def one(y, inp):
        c, wg, wu, wd = inp
        act = jax.nn.silu(dense._mm(h, wg, mode)) * dense._mm(h, wu, mode)
        return y + c[:, None] * dense._mm(act, wd, mode), None

    e = m["experts"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (coef.T, e["w_gate"], e["w_up"], e["w_down"]))
    return y, ids


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def _layer(lp, x, *, cfg, mode):
    nh, nkv, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    eps = cfg["eps"]
    s = x.shape[0]
    a = lp["attn"]
    h = dense._rms(x, lp["ln1"]["scale"], eps)
    q = dense._mm(h, a["wq"], mode).reshape(s, nh, hd)
    k = dense._mm(h, a["wk"], mode).reshape(s, nkv, hd)
    v = dense._mm(h, a["wv"], mode).reshape(s, nkv, hd)
    q = dense._rms(q, a["q_norm"]["scale"], eps)
    k = dense._rms(k, a["k_norm"]["scale"], eps)
    q, k = dense._rope(q, cfg["theta"]), dense._rope(k, cfg["theta"])
    x = x + dense._mm(dense._attention(q, k, v, mode), a["wo"], mode)
    y, ids = _experts(lp["moe"], dense._rms(x, lp["ln2"]["scale"], eps),
                      cfg, mode)
    return x + y, ids


def _static(cfg: dict):
    return dense._Frozen(heads=cfg["num_attention_heads"],
                         kv_heads=cfg["num_key_value_heads"],
                         head_dim=cfg["head_dim"], eps=cfg["rms_norm_eps"],
                         theta=float(cfg["rope_theta"]),
                         top_k=cfg["num_experts_per_tok"],
                         norm_topk_prob=cfg["norm_topk_prob"],
                         first=cfg["first_expert"])


def _forward(cfg: dict, params, tokens: np.ndarray, mode: str):
    """Hidden states of ``tokens`` (padded as ``dense.logits`` pads them)
    and each layer's top-k expert ids."""
    static = _static(cfg)
    n = tokens.shape[0]
    padded = np.zeros(BLOCK * 2 ** int(np.ceil(np.log2(-(-n // BLOCK)))),
                      np.int32)
    padded[:n] = tokens
    x = params["embed"][jnp.asarray(padded)].astype(jnp.float32)
    routes = []
    for i in range(cfg["num_hidden_layers"]):
        x, ids = _layer(dense._take(params["layers"], i), x, cfg=static,
                        mode=mode)
        routes.append(ids[:n])
    return x, routes


def logits(cfg: dict, params, tokens: np.ndarray, rows: np.ndarray,
           mode: str = "f32") -> jax.Array:
    """Teacher-forced logits (len(rows), V) float32 at positions ``rows``
    of ``tokens`` (one sequence), layer by layer as in ``dense.logits``."""
    x, _ = _forward(cfg, params, tokens, mode)
    return dense._logits(params, x, jnp.asarray(rows, jnp.int32),
                         eps=cfg["rms_norm_eps"], mode=mode)


def routes(cfg: dict, params, tokens: np.ndarray,
           mode: str = "f32") -> np.ndarray:
    """Each layer's top-k expert ids at every position of ``tokens``:
    (layers, len(tokens), k), for counting routing flips against the
    program."""
    _, ids = _forward(cfg, params, tokens, mode)
    return np.stack([np.asarray(i) for i in ids])
