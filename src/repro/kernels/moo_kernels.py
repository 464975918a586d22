"""Pallas tiled dominance-count kernel for the device NSGA-II engine (fastmoo).

Non-dominated sorting is the per-generation hot spot of an on-device NSGA-II:
every front-peeling round needs, for each point, the number of still-active
points that constraint-dominate it.  The naive formulation compares all pairs
at once and materializes a ``(P, P, n_obj)`` comparison tensor; this kernel
computes the same counts tile-by-tile so only a ``(Ti, Tj)`` comparison tile
ever exists at a time, mirroring ``char_kernels``/``app_kernels`` (interpret
mode is the validated CPU path, the XLA twin in ``core.fastmoo`` is the
off-TPU fast path).

Constraint domination (matching ``moo.fast_nondominated_sort``): j dominates i
iff

  * both feasible (viol <= 0) and j's objectives weakly dominate i's with at
    least one strict improvement, or
  * j is feasible and i is not, or
  * both infeasible and viol_j < viol_i.

Inputs are passed twice (row tile and column tile of the same arrays), like a
self-attention kernel:

  i side: objs (P, n_obj) f32, viol (P, 1) f32;
  j side: objs.T (n_obj, P) f32, viol.T (1, P) f32, active (1, P) i32 mask --
  only active *dominators* are counted (every row of the output is computed).

Block layout is 2-D-friendly: the comparison tile is ``(tile, j_tile)`` with
the **dominator** (j) axis innermost, so with the registry default
``j_tile=128`` every tile maps onto full TPU vector lanes instead of the
lane-hostile ``(tile, 1)`` columns of the original square tiling.  Both tile
sizes come from the kernel registry (spec ``"fastmoo.pallas"``; ``None``
resolves the bucket defaults, tuned contexts hand winners down through
``fastmoo.constraint_ranks``), as do the ``pl.CostEstimate`` and compiler
params (i is ``parallel``, j ``arbitrary``: it accumulates into a revisited
output block).

Output: (P, 1) int32 -- per-point count of active dominators.  Grid is
``(P // tile, P // j_tile)``; P must divide by both tiles (fastmoo pads with
inactive +inf-violation points, which are infeasible, inactive and never
counted).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import registry

__all__ = ["dominance_counts_pallas"]


def _kernel(oi_ref, vi_ref, oj_ref, vj_ref, aj_ref, out_ref, *, n_obj: int):
    """One (i, j) step: count active j-tile dominators of each i-tile point.

    The i side arrives as columns ((Ti, n_obj), (Ti, 1)) and the j side as
    lane rows ((n_obj, Tj), (1, Tj)), so every comparison is a plain
    (Ti, 1) x (1, Tj) broadcast with no relayout.
    """
    j = pl.program_id(1)

    vi = vi_ref[...]                             # (Ti, 1)
    vj = vj_ref[...]                             # (1, Tj)
    fi = vi <= 0.0
    fj = vj <= 0.0

    le = None
    lt = None
    for k in range(n_obj):                       # static unroll over objectives
        ok_i = oi_ref[:, pl.ds(k, 1)]            # (Ti, 1)
        ok_j = oj_ref[pl.ds(k, 1), :]            # (1, Tj)
        le_k = ok_j <= ok_i                      # (Ti, Tj): j lanes innermost
        lt_k = ok_j < ok_i
        le = le_k if le is None else le & le_k
        lt = lt_k if lt is None else lt | lt_k

    obj_dom = le & lt
    dom = fi & fj & obj_dom
    dom |= ~fi & fj
    dom |= ~fi & ~fj & (vj < vi)

    act = aj_ref[...] != 0                       # (1, Tj)
    part = (dom & act).astype(jnp.int32).sum(axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = part

    @pl.when(j > 0)
    def _acc():
        out_ref[...] += part


@functools.partial(jax.jit, static_argnames=("tile", "j_tile", "interpret"))
def dominance_counts_pallas(
    objs: jnp.ndarray,            # (P, n_obj) f32
    viol: jnp.ndarray,            # (P,) f32
    active: jnp.ndarray,          # (P,) bool/i32 -- dominators to count
    tile: int | None = None,
    j_tile: int | None = None,
    interpret: bool = True,
) -> jnp.ndarray:
    """Per-point count of active constraint-dominators: (P,) int32.

    P must divide by ``tile`` and ``j_tile`` (fastmoo pads with inactive
    +inf-violation points); ``None`` tiles resolve the registry defaults for
    this population bucket.
    """
    p, n_obj = objs.shape
    spec = registry.get("fastmoo.pallas")
    if tile is None or j_tile is None:
        tiles = spec.default_tiles(spec.bucket(p=p, n_obj=n_obj))
        tile = (tiles["tile"] if tile is None else tile)
        j_tile = (tiles["j_tile"] if j_tile is None else j_tile)
    tile, j_tile = min(tile, p), min(j_tile, p)
    assert p % tile == 0, (p, tile)
    assert p % j_tile == 0, (p, j_tile)
    objs = objs.astype(jnp.float32)
    v2 = viol.astype(jnp.float32).reshape(p, 1)

    cost = spec.cost_estimate(p=p, n_obj=n_obj)
    params = spec.compiler_params(tile=tile, j_tile=j_tile, n_obj=n_obj)
    grid = (p // tile, p // j_tile)
    out = pl.pallas_call(
        functools.partial(_kernel, n_obj=n_obj),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, n_obj), lambda i, j: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((n_obj, j_tile), lambda i, j: (0, j)),
            pl.BlockSpec((1, j_tile), lambda i, j: (0, j)),
            pl.BlockSpec((1, j_tile), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tile, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((p, 1), jnp.int32),
        cost_estimate=pl.CostEstimate(**cost),
        compiler_params=params,
        interpret=interpret,
    )(objs, v2, objs.T, v2.T, active.astype(jnp.int32).reshape(1, p))
    return out[:, 0]
