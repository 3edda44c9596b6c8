"""Logical-axis -> mesh-axis sharding rules.

Parallelism map (DESIGN.md §5):
  * DP   : batch over ("pod", "data")     — cross-pod gradient all-reduce
  * FSDP : weight "embed" dim over "data" — all-gather per layer under scan
  * TP   : "ff"/"heads"/"vocab"/"inner" over "model"
  * EP   : "experts" over "model" (shard_map all-to-all/psum dispatch)
  * SP   : "kv_seq" over "data" for long-context decode (flash-decoding merge)

Per-leaf divisibility: a mesh axis is dropped for a dimension it does not
divide (e.g. 12 attention heads on a 16-way model axis -> replicated heads,
noted per-arch in EXPERIMENTS.md).  Duplicate mesh axes within one leaf keep
the first occurrence (e.g. MoE weights: "experts"->model wins over
"ff"->model).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.params import ParamSpec, tree_map_specs

@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Threaded through model forward fns; None mesh = single-device."""
    mesh: Any = None                       # jax.sharding.Mesh | None
    pod_axis: str | None = "pod"           # None on single-pod meshes
    data_axis: str = "data"
    model_axis: str = "model"
    moe_impl: str = "auto"                 # "auto" | "dense" | "sharded"
    attn_impl: str = "blocked"             # "blocked" | "dot" | "flash"
    seq_shard_kv: bool = False             # SP: shard kv_seq over data
    remat: bool = False                    # checkpoint each layer-group body
    moe_decode_cf: float = 8.0             # looser capacity for tiny decode T
    replicate_lm_head: bool = False        # tied-embed archs: kill the
                                           # d-sharded head psum (hillclimb)
    fsdp_pod: bool = False                 # FSDP over (pod, data): shard
                                           # params/opt over ALL devices

    @property
    def batch_axes(self) -> tuple[str, ...]:
        axes = []
        if self.pod_axis and self.mesh is not None \
                and self.pod_axis in self.mesh.axis_names:
            axes.append(self.pod_axis)
        axes.append(self.data_axis)
        return tuple(axes)

    def axis_size(self, axes) -> int:
        if self.mesh is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        return math.prod(self.mesh.shape[a] for a in axes)

    def constrain(self, x, spec: P):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def batch_spec(self, ndim: int, batch_dim: int = 0) -> P:
        parts: list = [None] * ndim
        parts[batch_dim] = self.batch_axes
        return P(*parts)


def default_rules(ctx: ShardCtx, *, mode: str = "train") -> dict[str, Any]:
    """logical axis -> mesh axis (or tuple).  mode: "train" | "serve"."""
    ba = ctx.batch_axes
    rules = {
        "batch": ba,
        "embed": ((tuple(ba) if ctx.fsdp_pod and len(ba) > 1
                   else ctx.data_axis)
                  if mode == "train" else None),             # FSDP
        "ff": ctx.model_axis,
        "heads": ctx.model_axis,
        "kv_heads": ctx.model_axis,
        "vocab": ctx.model_axis,
        "vocab_tbl": None,                  # gather stays local (see layers)
        "embed_tbl": None if ctx.replicate_lm_head else ctx.model_axis,
        # a2a EP shards whole experts over (data x model); 2D EP shards the
        # expert ffn dim over data instead (both serve-scale layouts)
        "experts": ((ctx.data_axis, ctx.model_axis)
                    if mode == "serve" and ctx.moe_impl == "sharded_a2a"
                    else ctx.model_axis),
        "expert_ff": (ctx.data_axis if mode == "serve"
                      and ctx.moe_impl == "sharded2d" else None),
        "inner": ctx.model_axis,
        "q_lora": None,
        "kv_lora": None,
        "layers": None,
        "kv_seq": (None if not ctx.seq_shard_kv else
                   ctx.data_axis if ctx.seq_shard_kv is True else
                   ctx.seq_shard_kv),
    }
    return rules


def spec_for(leaf: ParamSpec, rules: Mapping[str, Any], mesh: Mesh) -> P:
    """PartitionSpec for one ParamSpec with divisibility + dup filtering."""
    if not leaf.axes or mesh is None:
        return P()
    used: set[str] = set()
    parts = []
    for dim, logical in zip(leaf.shape, leaf.axes):
        axis = rules.get(logical) if logical else None
        if axis is None:
            parts.append(None)
            continue
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        kept = []
        size = 1
        for a in axes:
            if a in used:
                continue
            size *= mesh.shape[a]
            kept.append(a)
        if kept and dim % math.prod(mesh.shape[a] for a in kept) == 0:
            used.update(kept)
            parts.append(tuple(kept) if len(kept) > 1 else kept[0])
        else:
            parts.append(None)
    return P(*parts)


def partition_tree(specs, rules: Mapping[str, Any], mesh: Mesh):
    """ParamSpec tree -> PartitionSpec tree."""
    return tree_map_specs(lambda s: spec_for(s, rules, mesh), specs)


def sharding_tree(specs, rules, mesh: Mesh):
    """ParamSpec tree -> NamedSharding tree."""
    return tree_map_specs(
        lambda s: NamedSharding(mesh, spec_for(s, rules, mesh)), specs)
