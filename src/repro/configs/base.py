"""Architecture & run configuration dataclasses.

One ``ArchConfig`` instance per assigned architecture lives in
``src/repro/configs/<id>.py``; ``reduced()`` derives the smoke-test scale
variant of the same family (small layers/width/experts/vocab) used by the
per-arch CPU tests.  The full configs are only ever lowered abstractly
(ShapeDtypeStruct) by the dry-run.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    n_shared_experts: int = 0
    d_ff_shared: int = 0          # total shared-expert hidden width
    capacity_factor: float = 1.25         # training dispatch only
    router_aux_weight: float = 0.01
    router_z_weight: float = 1e-3
    norm_topk_prob: bool = True           # top-k weights divided by their sum
    # the experts this chip holds: ``experts_held`` of them (0: all
    # ``n_experts``) starting at ``first_expert``; routing still spans all
    # ``n_experts``, and rows routed elsewhere add nothing here
    experts_held: int = 0
    first_expert: int = 0

    @property
    def held(self) -> int:
        return self.experts_held or self.n_experts


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    expand: int = 2
    headdim: int = 64
    chunk: int = 256
    conv_width: int = 4
    n_groups: int = 1

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.headdim


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None      # default: d_model // n_heads
    act: str = "silu_gated"             # silu_gated | relu2 | gelu
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid (hymba): parallel attn+ssm heads; sliding window + global layers
    attn_window: Optional[int] = None   # sliding-window width for SWA layers
    n_global_layers: int = 0            # hymba: layers with full attention
    # enc-dec (whisper): n_layers == decoder layers
    n_enc_layers: int = 0
    enc_seq: int = 1500                 # stubbed frame-embedding length
    # vlm (llava): stubbed patch embeddings prepended to the text sequence
    n_patch_tokens: int = 0
    # numerics
    param_dtype: str = "bfloat16"
    act_dtype: str = "bfloat16"
    # capability flags
    subquadratic: bool = False          # can run long_500k
    max_seq: int = 32_768

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def adtype(self):
        return jnp.dtype(self.act_dtype)

    def n_params(self) -> int:
        """Total parameter count (embedding included)."""
        d, hd = self.d_model, self.hd
        attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) \
            + self.n_heads * hd * d
        if self.qk_norm:
            attn += 2 * hd
        if self.act == "silu_gated":
            mlp = 3 * d * self.d_ff
        else:
            mlp = 2 * d * self.d_ff
        if self.moe:
            me = self.moe
            emlp = (3 * d * me.d_ff_expert) * me.held   # held here
            if me.n_shared_experts:
                emlp += 3 * d * me.d_ff_shared + d  # + shared gate
            emlp += d * me.n_experts                # router
            mlp = emlp
        per_layer = attn + mlp + 2 * d
        if self.family == "ssm":
            s = self.ssm
            di = s.d_inner(d)
            nh = s.n_heads(d)
            gn = s.n_groups * s.d_state
            per_layer = (d * (2 * di + 2 * gn + nh)       # in projections
                         + s.conv_width * (di + 2 * gn)   # depthwise conv
                         + 2 * nh + nh                    # A_log, dt_bias, D
                         + di + di * d + 2 * d)           # norm + out + lns
        if self.family == "hybrid":
            s = self.ssm
            di = s.d_inner(d)
            nh = s.n_heads(d)
            gn = s.n_groups * s.d_state
            ssm_p = (d * (2 * di + 2 * gn + nh) + s.conv_width * (di + 2 * gn)
                     + 2 * nh + nh + di + di * d)
            per_layer = attn + ssm_p + mlp + 3 * d
        total = self.n_layers * per_layer
        total += self.vocab * d                      # embed
        if not self.tie_embeddings:
            total += self.vocab * d                  # lm head
        total += d                                   # final norm
        if self.family == "encdec":
            enc_attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) \
                + self.n_heads * hd * d
            enc_layer = enc_attn + mlp + 2 * d
            cross = attn
            total += self.n_enc_layers * enc_layer + self.n_layers * cross \
                + self.n_layers * d + self.enc_seq * d  # extra ln + enc pos
        return int(total)

    def n_active_params(self) -> int:
        """Parameters touched per token (MoE: top-k + shared only)."""
        if not self.moe:
            return self.n_params()
        me = self.moe
        d = self.d_model
        dense_like = dataclasses.replace(self, moe=None, d_ff=0)
        base = dense_like.n_params()
        active_mlp = 3 * d * me.d_ff_expert * me.top_k
        if me.n_shared_experts:
            active_mlp += 3 * d * me.d_ff_shared + d
        active_mlp += d * me.n_experts
        return int(base + self.n_layers * active_mlp)

    def reduced(self) -> "ArchConfig":
        """Smoke-test scale config of the same family."""
        kw = dict(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            vocab=256, head_dim=16, max_seq=128,
        )
        if self.moe:
            kw["moe"] = dataclasses.replace(
                self.moe, n_experts=4, top_k=2, d_ff_expert=32,
                d_ff_shared=64 if self.moe.n_shared_experts else 0,
                n_shared_experts=min(self.moe.n_shared_experts, 2))
        if self.ssm:
            kw["ssm"] = dataclasses.replace(
                self.ssm, d_state=8, headdim=16, chunk=16)
            if self.family == "ssm":
                kw["n_heads"] = 8      # d_inner(64)=128 / headdim 16
                kw["n_kv_heads"] = 8
        if self.family == "hybrid":
            kw["n_heads"], kw["n_kv_heads"] = 4, 2
            kw["attn_window"] = 32
            kw["n_global_layers"] = 1
        if self.family == "encdec":
            kw["n_enc_layers"] = 2
            kw["enc_seq"] = 24
        if self.family == "vlm":
            kw["n_patch_tokens"] = 12
        return dataclasses.replace(self, **kw)


def tiny_family_configs(*, d_model: int = 32, vocab: int = 97,
                        max_seq: int = 64,
                        name_prefix: str = "tiny-fam") -> dict:
    """Reduced MoE/SSM/hybrid configs pinning ONE shared serving-test
    regime (used by tests/conftest.py and benchmarks/bench_serving.py so
    the regime cannot drift between the suites and the bench claims).

    MoE serves through the dropless expert layer (``models/moe.py``):
    every routed row is computed, so MoE logits are per-token and chunked
    or batched serving matches sequential generation without any capacity
    setting."""
    hd = d_model // 4
    f32 = dict(param_dtype="float32", act_dtype="float32")
    return {
        "hybrid": ArchConfig(name=f"{name_prefix}-hybrid", family="hybrid",
                             n_layers=3, d_model=d_model, n_heads=4,
                             n_kv_heads=2, d_ff=2 * d_model, vocab=vocab,
                             head_dim=hd,
                             ssm=SSMConfig(d_state=8, headdim=hd, chunk=16),
                             attn_window=8, n_global_layers=1,
                             subquadratic=True, max_seq=max_seq, **f32),
        "moe": ArchConfig(name=f"{name_prefix}-moe", family="moe",
                          n_layers=2, d_model=d_model, n_heads=4,
                          n_kv_heads=2, d_ff=2 * d_model, vocab=vocab,
                          head_dim=hd,
                          moe=MoEConfig(n_experts=4, top_k=2,
                                        d_ff_expert=32),
                          max_seq=max_seq, **f32),
        "ssm": ArchConfig(name=f"{name_prefix}-ssm", family="ssm",
                          n_layers=2, d_model=d_model, n_heads=8,
                          n_kv_heads=8, d_ff=2 * d_model, vocab=vocab,
                          ssm=SSMConfig(d_state=8, headdim=hd, chunk=16),
                          subquadratic=True, max_seq=max_seq, **f32),
    }


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One cell of the assigned (arch × shape) grid."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode

SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}
