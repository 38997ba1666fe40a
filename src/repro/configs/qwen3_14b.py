"""qwen3-14b — dense, qk_norm, GQA [hf:Qwen/Qwen3-14B]."""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="qwen3-14b",
    family="dense",
    n_layers=40,
    d_model=5120,
    n_heads=40,
    n_kv_heads=8,
    d_ff=17408,
    vocab=151_936,
    act="silu_gated",
    qk_norm=True,
    rope_theta=1_000_000.0,
    rms_eps=1e-6,
    max_seq=32_768,
)
