"""Lane-axis sharding rules (paper §IV.A — C1) for the cluster-scale mapping.

One TPU chip plays the role of one Ara lane: its HBM/VMEM is the lane's VRF
chunk, the ICI torus is the slide network, the MXU is the VMFPU.  The paper's
split-VRF argument (interconnect O(ℓ) when traffic is lane-local vs O(ℓ²) for
a monolithic VRF) becomes: keep tensors sharded so each op reads operands
resident on its own chip, and restrict cross-lane traffic to explicit,
scheduled collectives (slide unit = collective_permute, mask unit = the only
broadcast-style consumer, VLSU = data loading over `data`).

``LogicalRules`` maps *logical* tensor axes to mesh axes; model code annotates
tensors with logical names only, so the same model runs on any mesh (single
pod, multi pod, or a test mesh) — and on a 1-device CPU mesh everything
degrades to replicated, which is how smoke tests run.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# Canonical mesh axis names (see launch/mesh.py).
POD_AXIS = "pod"
DATA_AXIS = "data"
LANE_AXIS = "model"   # the lane axis (C1)

# Logical axis -> mesh axes. None = replicated.
DEFAULT_RULES: dict[str, Optional[tuple[str, ...]]] = {
    # activations
    "batch": (POD_AXIS, DATA_AXIS),   # DP over pods × data
    "seq": None,                      # default: replicated (SP overrides)
    "seq_shard": (DATA_AXIS,),        # sequence parallelism (long context)
    # Megatron-style TP sequence parallelism: the residual stream between
    # TP blocks is sharded over the lane axis, turning the per-layer f32
    # activation all-reduce into reduce-scatter + bf16 all-gather and
    # sharding norm compute + remat-saved activations.  Off by default
    # (paper-faithful baseline); enable with with_rules(seq_tp=("model",))
    # or `--rule seq_tp=model` in the dry-run (§Perf iteration 2).
    "seq_tp": None,
    "embed": None,                    # d_model of activations stays unsharded
    "heads": (LANE_AXIS,),            # attention heads over lanes (TP)
    "kv_heads": (LANE_AXIS,),
    "ffn": (LANE_AXIS,),              # MLP hidden over lanes (TP)
    "vocab_tp": (LANE_AXIS,),         # embedding/LM-head vocab over lanes
    "expert": (LANE_AXIS,),           # MoE experts over lanes (EP)
    "capacity": (DATA_AXIS,),         # MoE capacity over data
    # Decode KV cache: *sequence* over lanes (flash-decode).  Each lane
    # attends over its KV slice; the softmax combine is a tiny per-layer
    # cross-lane reduction — the paper's 3-step reduction (C4) applied to
    # attention.  The alternative (kv-heads over lanes) is undersized for
    # GQA (kv_heads < lanes ⇒ replication ⇒ the full cache all-gathered
    # per step, §Perf cell-3 baseline profile).
    "kv_seq": (LANE_AXIS,),
    # weights
    "embed_w": None,
    "zero1": (DATA_AXIS,),            # optimizer-state sharding (ZeRO-1)
    "ssm_state": None,
    "ssm_heads": (LANE_AXIS,),
    # fused batch·ssm-head dim of the decode-time SSD state
    "ssm_bh": (POD_AXIS, DATA_AXIS, LANE_AXIS),
}


@dataclasses.dataclass(frozen=True)
class LogicalRules:
    rules: dict = dataclasses.field(default_factory=lambda: dict(DEFAULT_RULES))
    mesh_axes: tuple = (POD_AXIS, DATA_AXIS, LANE_AXIS)

    def spec(self, *logical_axes: Optional[str]) -> P:
        """PartitionSpec for a tensor described by logical axis names.

        Mesh axes not present in the mesh are dropped (so specs written for
        the 3-axis production mesh work on the 2-axis single-pod mesh and on
        1-device test meshes).
        """
        parts = []
        for name in logical_axes:
            if name is None:
                parts.append(None)
                continue
            axes = self.rules.get(name)
            if axes is None:
                parts.append(None)
            else:
                kept = tuple(a for a in axes if a in self.mesh_axes)
                parts.append(kept if len(kept) != 1 else kept[0])
        return P(*parts)

    def for_mesh(self, mesh: Mesh) -> "LogicalRules":
        return dataclasses.replace(self, mesh_axes=tuple(mesh.axis_names))

    def sharding(self, mesh: Mesh, *logical_axes: Optional[str]) -> NamedSharding:
        return NamedSharding(mesh, self.for_mesh(mesh).spec(*logical_axes))


def with_rules(**overrides) -> LogicalRules:
    """DEFAULT_RULES with per-experiment overrides (perf-iteration knob)."""
    rules = dict(DEFAULT_RULES)
    rules.update(overrides)
    return LogicalRules(rules=rules)


def constrain(x: jax.Array, rules: LogicalRules, *logical_axes) -> jax.Array:
    """``lax.with_sharding_constraint`` via logical names.

    No-op when tracing without a mesh (unit tests / single device), so model
    code can sprinkle constraints unconditionally.  Inside a partial-auto
    ``shard_map`` (the explicit-reduction train step), axes that are Manual
    are dropped from the spec — the constraint then only refers to the
    still-auto (GSPMD) axes, e.g. the lane axis.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    auto_axes = tuple(
        name for name, ty in zip(mesh.axis_names, mesh.axis_types)
        if ty != AxisType.Manual)
    if not auto_axes:
        return x
    rules = dataclasses.replace(rules, mesh_axes=auto_axes)
    return jax.lax.with_sharding_constraint(x, rules.spec(*logical_axes))
