"""Logical-axis sharding (MaxText-style rule tables).

Params and activations are annotated with *logical* axis names; a rule table maps
each logical name to zero or more mesh axes.  Two tables exist because FSDP shards
the same logical dim of a *weight* differently from the matching activation dim.

Mesh axes: ``pod`` (multi-pod only), ``data``, ``model``.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ShardingRules", "BASE_RULES", "logical_pspec", "constrain",
    "named_sharding",
]

MeshAxes = tuple[str, ...]


@dataclass(frozen=True)
class ShardingRules:
    """logical name -> tuple of mesh axes (() = replicated)."""

    param_rules: dict[str, MeshAxes] = field(default_factory=dict)
    act_rules: dict[str, MeshAxes] = field(default_factory=dict)

    def with_fsdp(self) -> "ShardingRules":
        """ZeRO-3-style: additionally shard weight 'embed'/'ff_in' dims over data."""
        pr = dict(self.param_rules)
        pr["embed"] = ("data",)
        pr["expert_ff"] = ("data",)   # second expert dim: EP over model, FSDP over data
        return replace(self, param_rules=pr)

    def with_overrides(self, param: dict | None = None, act: dict | None = None) -> "ShardingRules":
        pr = dict(self.param_rules)
        pr.update(param or {})
        ar = dict(self.act_rules)
        ar.update(act or {})
        return ShardingRules(param_rules=pr, act_rules=ar)

    def resolve(self, axes: tuple[str | None, ...], kind: str = "param") -> P:
        table = self.param_rules if kind == "param" else self.act_rules
        used: set[str] = set()
        parts = []
        for name in axes:
            if name is None:
                parts.append(None)
                continue
            mesh_axes = tuple(a for a in table.get(name, ()) if a not in used)
            used.update(mesh_axes)
            if len(mesh_axes) == 0:
                parts.append(None)
            elif len(mesh_axes) == 1:
                parts.append(mesh_axes[0])
            else:
                parts.append(mesh_axes)
        return P(*parts)


BASE_RULES = ShardingRules(
    param_rules={
        # weight dims
        "embed": (),              # replicated unless FSDP
        "vocab": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "mlp": ("model",),
        "experts": ("model",),    # expert parallelism
        "expert_ff": (),
        "stack": (),              # scan-stacked layer axis: never sharded
        "ssm_inner": ("model",),
        "lora": (),
        "head_dim": (),
    },
    act_rules={
        "batch": ("pod", "data"),
        "seq": (),
        "res_seq": (),            # residual-stream seq: ("model",) = Megatron-SP
        "kv_seq": (),             # decode KV caches: ("model",) / ("data","model")
        "kv_enc": (),             # cross-attention KV length (encoder/image tokens)
        "embed": (),
        "heads": ("model",),
        "kv_heads": (),           # KV heads (<= mesh model size only rarely): repl.
        "mlp": ("model",),
        "vocab": ("model",),
        "experts": ("model",),
        "capacity": (),
        "ssm_inner": ("model",),
        "ssm_heads": ("model",),
        "head_dim": (),
        "lora": (),
    },
)


def logical_pspec(rules: ShardingRules, axes: tuple[str | None, ...], kind: str) -> P:
    return rules.resolve(axes, kind)


def named_sharding(mesh: Mesh, spec: P, shape: tuple[int, ...] | None = None) -> NamedSharding:
    """NamedSharding with two safeguards:

    * mesh axes the mesh doesn't have are dropped ('pod' on the single-pod mesh);
    * if ``shape`` is given, axes whose product doesn't divide the dim are
      pruned greedily (jit in_shardings demand exact divisibility -- e.g. a
      batch-1 long-context cache can't shard its batch dim).
    """

    def keep(i: int, part):
        if part is None:
            return None
        parts = part if isinstance(part, tuple) else (part,)
        parts = tuple(p for p in parts if p in mesh.axis_names)
        if shape is not None:
            kept = []
            dim = shape[i]
            for p in parts:
                n = mesh.shape[p]
                if dim % n == 0:
                    kept.append(p)
                    dim //= n
            parts = tuple(kept)
        if not parts:
            return None
        return parts[0] if len(parts) == 1 else parts

    return NamedSharding(mesh, P(*(keep(i, p) for i, p in enumerate(spec))))


def constrain(x, rules: ShardingRules, *axes: str | None):
    """with_sharding_constraint via logical activation axes (no-op off-mesh)."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    spec = rules.resolve(tuple(axes), kind="act")
    return jax.lax.with_sharding_constraint(x, named_sharding(mesh, spec))


def _current_mesh():
    """The mesh ``jax.set_mesh`` put in scope, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m
