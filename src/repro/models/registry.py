"""Architecture registry: ``--arch <id>`` -> config + model + input specs.

One entry per assigned architecture (plus the paper's own vector unit, which
is not an LM and lives in ``configs/ara_vu.py`` for the paper-table benches).

``build(name)`` returns a :class:`Bundle` whose ``model`` exposes the common
driver surface (init / loss_fn / init_cache / prefill / decode_step), and
whose ``input_specs(shape)`` produces weak-type-correct ShapeDtypeStruct
stand-ins for every model input of that grid cell — the dry-run lowers
against these without ever allocating device memory.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import (ara_vu, deepseek_coder_33b, hymba_1_5b, llama3_2_3b, llava_next_34b, mamba2_2_7b, nemotron_4_15b, qwen2_moe_a2_7b, qwen3_14b, qwen3_moe_30b_a3b, whisper_large_v3)
from repro.configs.base import ArchConfig, ShapeConfig, SHAPES
from repro.models import hybrid as H
from repro.models import mamba2 as S
from repro.models import moe as M
from repro.models import transformer as T
from repro.models.encdec import EncDecLM
from repro.models.vlm import VLM, patch_embed_stub

_CONFIGS: dict[str, ArchConfig] = {
    c.CONFIG.name: c.CONFIG
    for c in (deepseek_coder_33b, nemotron_4_15b, qwen3_14b, llama3_2_3b,
              hymba_1_5b, llava_next_34b, mamba2_2_7b, whisper_large_v3,
              qwen3_moe_30b_a3b, qwen2_moe_a2_7b)
}

ARCH_NAMES: tuple[str, ...] = tuple(sorted(_CONFIGS))
VECTOR_UNIT = ara_vu.CONFIG


def config(name: str) -> ArchConfig:
    try:
        return _CONFIGS[name]
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_NAMES}") from None


def build_model(cfg: ArchConfig, rules=None):
    """Instantiate the family driver for a config (full or reduced)."""
    kw = {} if rules is None else {"rules": rules}

    def bind(fn):
        # serving hooks take rules keyword-only: bind the model's rules
        # (mirroring the dense adapters, which pass rules=self.rules) so a
        # custom-rules build constrains the non-dense hot path identically
        return functools.partial(fn, **kw) if kw else fn

    if cfg.family == "dense":
        return T.LM(cfg, **kw)
    if cfg.family == "vlm":
        return VLM(cfg, **kw)
    if cfg.family == "moe":
        lm = T.LM(
            cfg,
            layer_init=M.moe_layer_init,
            layer_apply=lambda p, c, x, extra, **k: M.moe_layer_apply(
                p, c, x, extra, positions=k["positions"]),
            layer_chunk=bind(M.moe_layer_chunk),
            chunk_scatter=T.dense_chunk_scatter,
            layer_decode_rows=bind(M.moe_layer_decode_rows),
            rows_scatter=T.dense_rows_scatter, **kw)
        lm._prefill_layer = lambda lp, c, x, cache_l, positions, extra: \
            M.moe_prefill_layer(lp, c, x, cache_l, positions, extra,
                                rules=lm.rules)
        lm.counters = M.COUNTERS
        return lm
    if cfg.family == "ssm":
        lm = T.LM(
            cfg,
            layer_init=S.ssm_layer_init,
            layer_apply=lambda p, c, x, extra, **k: S.ssm_layer_apply(
                p, c, x, extra),
            layer_chunk=bind(S.ssm_layer_chunk),
            chunk_scatter=S.ssm_chunk_scatter,
            layer_decode_rows=bind(S.ssm_layer_decode_rows),
            rows_scatter=S.ssm_rows_scatter,
            init_layer_cache=S.init_ssm_cache, **kw)
        lm._prefill_layer = lambda lp, c, x, cache_l, positions, extra: \
            S.ssm_prefill_layer(lp, c, x, cache_l, positions, extra)
        return lm
    if cfg.family == "hybrid":
        lm = T.LM(
            cfg,
            layer_init=H.hybrid_layer_init,
            layer_apply=lambda p, c, x, extra, **k: H.hybrid_layer_apply(
                p, c, x, extra, positions=k["positions"]),
            layer_chunk=bind(H.hybrid_layer_chunk),
            chunk_scatter=H.hybrid_chunk_scatter,
            layer_decode_rows=bind(H.hybrid_layer_decode_rows),
            rows_scatter=H.hybrid_rows_scatter,
            init_layer_cache=H.init_hybrid_cache,
            layer_xs_fn=H.window_schedule, **kw)
        lm._prefill_layer = lambda lp, c, x, cache_l, positions, extra: \
            H.hybrid_prefill_layer(lp, c, x, cache_l, positions, extra,
                                   rules=lm.rules)
        return lm
    if cfg.family == "encdec":
        return EncDecLM(cfg, **kw)
    raise ValueError(f"unknown family {cfg.family!r}")


# ---------------------------------------------------------------------------
# Shape-grid applicability (DESIGN.md §Shape-grid skips)
# ---------------------------------------------------------------------------

def cell_supported(cfg: ArchConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """(supported?, reason-if-not) for one (arch × shape) grid cell."""
    if shape.seq_len > 32_768 and not cfg.subquadratic:
        return False, ("long_500k needs sub-quadratic attention; "
                       f"{cfg.name} is pure full-attention (DESIGN.md)")
    return True, ""


def grid_cells(*, include_skips: bool = False):
    """All (arch, shape) cells; 32 runnable + 8 documented skips."""
    for name in ARCH_NAMES:
        cfg = _CONFIGS[name]
        for shape in SHAPES.values():
            ok, why = cell_supported(cfg, shape)
            if ok or include_skips:
                yield name, shape.name, ok, why


# ---------------------------------------------------------------------------
# Abstract input specs (dry-run; no allocation)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def train_batch_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    b, s = shape.global_batch, shape.seq_len
    specs = {
        "tokens": _sds((b, s), jnp.int32),
        "labels": _sds((b, s), jnp.int32),
    }
    if cfg.family == "encdec":
        specs["frames"] = _sds((b, cfg.enc_seq, cfg.d_model), cfg.adtype)
    if cfg.family == "vlm":
        specs["prefix_embeds"] = patch_embed_stub(cfg, b)
        # loss runs on the text positions only; prefix trimmed inside loss_fn
    return specs


def prefill_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    """Inputs of ``prefill(params, tokens, cache, **extras)``."""
    b, s = shape.global_batch, shape.seq_len
    model = build_model(cfg)
    cache_len = s + (cfg.n_patch_tokens if cfg.family == "vlm" else 0)
    cache = jax.eval_shape(lambda: model.init_cache(b, cache_len))
    out = {"tokens": _sds((b, s), jnp.int32), "cache": cache}
    if cfg.family == "encdec":
        out["frames"] = _sds((b, cfg.enc_seq, cfg.d_model), cfg.adtype)
    if cfg.family == "vlm":
        out["patch_embeds"] = patch_embed_stub(cfg, b)
    return out


def decode_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    """Inputs of ``decode_step(params, token_t, cache, pos)`` with a KV
    cache of shape.seq_len (one new token against that context)."""
    b, s = shape.global_batch, shape.seq_len
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(b, s))
    return {
        "token_t": _sds((b,), jnp.int32),
        "cache": cache,
        "pos": _sds((b,), jnp.int32),
    }


def abstract_params(cfg: ArchConfig):
    """ShapeDtypeStruct pytree of the full model parameters."""
    model = build_model(cfg)
    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))


@dataclasses.dataclass(frozen=True)
class Bundle:
    name: str
    cfg: ArchConfig
    model: Any

    def input_specs(self, shape_name: str) -> dict:
        shape = SHAPES[shape_name]
        if shape.kind == "train":
            return train_batch_specs(self.cfg, shape)
        if shape.kind == "prefill":
            return prefill_specs(self.cfg, shape)
        return decode_specs(self.cfg, shape)


def build(name: str, *, reduced: bool = False, rules=None) -> Bundle:
    cfg = config(name)
    if reduced:
        cfg = cfg.reduced()
    return Bundle(name=name, cfg=cfg, model=build_model(cfg, rules=rules))
