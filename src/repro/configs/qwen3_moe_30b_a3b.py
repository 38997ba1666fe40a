"""qwen3-moe-30b-a3b — 128 experts top-8, qk_norm [hf:Qwen/Qwen3-30B-A3B].

head_dim=128 explicit (HF config; q-dim 4096 != d_model 2048).  Every
layer is MoE (``mlp_only_layers`` empty, ``decoder_sparse_step`` 1), no
shared expert, top-8 weights renormalised (``norm_topk_prob``); the
unused dense ``intermediate_size`` 6144 is not modelled.
"""
from repro.configs.base import ArchConfig, MoEConfig

CONFIG = ArchConfig(
    name="qwen3-moe-30b-a3b",
    family="moe",
    n_layers=48,
    d_model=2048,
    n_heads=32,
    n_kv_heads=4,
    d_ff=768,              # per-expert hidden width
    vocab=151_936,
    head_dim=128,
    act="silu_gated",
    qk_norm=True,
    rope_theta=1_000_000.0,
    rms_eps=1e-6,
    moe=MoEConfig(n_experts=128, top_k=8, d_ff_expert=768,
                  norm_topk_prob=True, router_aux_weight=0.001),
    max_seq=40_960,
)
