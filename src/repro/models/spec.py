"""Parameter-spec trees: declare shapes + logical axes once, then materialize as
real arrays (init), ShapeDtypeStructs (dry-run -- no allocation), or
PartitionSpecs (sharding)."""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .sharding import ShardingRules, named_sharding

__all__ = [
    "ParamSpec",
    "init_params",
    "abstract_params",
    "param_pspecs",
    "param_shardings",
    "count_params",
    "stacked",
]


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"          # normal | zeros | ones
    scale: float = 1.0            # stddev multiplier for normal init
    dtype: str | None = None      # override the tree-level dtype (e.g. fp32 states)

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")

    def resolved_dtype(self, default):
        return jnp.dtype(self.dtype) if self.dtype else default


def stacked(spec: ParamSpec, n: int) -> ParamSpec:
    """Add a leading scan-stack axis."""
    return ParamSpec(
        shape=(n, *spec.shape), axes=("stack", *spec.axes), init=spec.init,
        scale=spec.scale, dtype=spec.dtype,
    )


def _path_seed(path: str, base_seed: int) -> int:
    h = hashlib.blake2s(f"{base_seed}:{path}".encode(), digest_size=8).digest()
    return int.from_bytes(h, "little") % (2**63)


def _leaf_paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaf_paths(tree[k], f"{prefix}/{k}")
    else:
        yield prefix, tree


def init_params(tree, seed: int = 0, dtype=jnp.bfloat16):
    """Materialize a spec tree with deterministic per-leaf seeding."""

    def make(path: str, spec: ParamSpec):
        dt = spec.resolved_dtype(dtype)
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dt)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dt)
        key = jax.random.key(_path_seed(path, seed))
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        std = spec.scale / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dt)

    return _map_with_path(tree, make)


def abstract_params(tree, dtype=jnp.bfloat16):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.resolved_dtype(dtype)), tree
    )


def param_pspecs(tree, rules: ShardingRules, kind: str = "param"):
    return jax.tree.map(lambda s: rules.resolve(s.axes, kind=kind), tree)


def param_shardings(tree, rules: ShardingRules, mesh, kind: str = "param"):
    return jax.tree.map(
        lambda s: named_sharding(mesh, rules.resolve(s.axes, kind=kind), s.shape),
        tree,
    )


def count_params(tree) -> int:
    return sum(int(np.prod(s.shape)) for _, s in _leaf_paths(tree))


def _map_with_path(tree, fn, prefix=""):
    if isinstance(tree, dict):
        return {k: _map_with_path(v, fn, f"{prefix}/{k}") for k, v in tree.items()}
    return fn(prefix, tree)
