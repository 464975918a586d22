"""Unified kernel registry: one spec per Pallas/XLA implementation.

PRs 1-4 grew three accelerator kernel families -- the characterization BEHAV
reduction (``char_kernels.behav_stats_pallas`` + its XLA twin), the
application table-GEMV (``app_kernels.table_gemv_pallas`` + gather/GEMM
fallbacks) and the NSGA-II dominance counts (``moo_kernels.
dominance_counts_pallas`` + the dominance-matrix XLA twin) -- and each
hard-coded block shapes chosen for int32-overflow safety, not occupancy.
This module is the single place every implementation registers:

  * its **tunable block-shape space** (ordered ``(param, candidates)`` pairs),
  * **safe defaults** (a function of the shape bucket -- e.g. the char
    engine's int32-safe ``a_tile``),
  * a **constraint** predicate filtering candidates per shape bucket (int32
    partial-sum bounds, divisibility, VMEM fit),
  * **cost-estimate** and **compiler-params** formulas (the cost dict is
    wrapped into ``pl.CostEstimate`` by the kernel files; the params dict --
    dimension semantics + VMEM limits -- into ``pltpu.CompilerParams`` by
    :meth:`KernelSpec.compiler_params`, the one place that class is built),
  * a **correctness oracle** (the reference implementation every tuned tile
    candidate must match bit-for-bit under interpret mode; see
    ``kernels.tuning``).

The registry itself is pure data: importing it pulls in neither JAX nor the
kernel modules (implementations and oracles are referenced by
``"module:attr"`` strings and resolved lazily), so
``repro.core.engine.ExecutionContext`` can consult engine menus without
dragging device code into numpy-only processes.

Engines resolve implementations through
:meth:`repro.core.engine.ExecutionContext.resolve_impl` (which reads the
per-engine menus registered here) and tile shapes through
:func:`repro.kernels.tuning.tiles_for` (which honors the context's
``tuning="off"|"cached"|"search"`` policy).
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass, field
from typing import Callable

__all__ = [
    "KernelSpec",
    "register",
    "get",
    "specs_for",
    "impl_names",
    "registered",
    "describe",
    "ENGINES",
]

ENGINES = ("fastchar", "fastapp", "fastmoo", "axo_matmul", "flash_attention")


def _pow2_bucket(x: int, cap: int = 1 << 14) -> int:
    """Smallest power of two >= x (>= 1), capped -- the shape-bucket rule."""
    x = max(int(x), 1)
    b = 1
    while b < x and b < cap:
        b <<= 1
    return b


def _resolve_ref(ref: str):
    mod, attr = ref.split(":")
    return getattr(importlib.import_module(mod), attr)


@dataclass(frozen=True)
class KernelSpec:
    """One registered kernel implementation.

    ``fn_ref`` / ``oracle_ref`` are lazy ``"module:attr"`` references: ``fn``
    is the engine-level entry point the autotuner times (signature
    ``fn(bucket, tiles) -> outputs``, see ``kernels.tuning`` for the per-
    engine harnesses), ``oracle`` the reference implementation parity is
    checked against.  ``tunables`` is the ordered block-shape search space;
    ``defaults_fn(bucket)`` the safe (untuned) tiles; ``constraint(bucket,
    tiles)`` filters candidates; ``cost_fn`` / ``params_fn`` return plain
    dicts: the kernel files wrap the first into ``pl.CostEstimate``, and
    :meth:`compiler_params` wraps the second into ``pltpu.CompilerParams``.
    """

    name: str                                   # "fastchar.pallas", ...
    engine: str                                 # one of ENGINES
    impl: str                                   # "pallas" | "xla" | "gemm"
    fn_ref: str                                 # harness entry "module:attr"
    oracle_ref: str | None = None               # reference impl "module:attr"
    tunables: tuple = ()                        # ((param, (candidates...)),...)
    defaults_fn: Callable | None = None         # bucket -> {param: value}
    bucket_fn: Callable | None = None           # (**shape) -> hashable bucket
    constraint: Callable | None = None          # (bucket, tiles) -> bool
    cost_fn: Callable | None = None             # (shape kwargs) -> dict
    params_fn: Callable | None = None           # (shape kwargs) -> dict
    tol: float = 1e-6                           # rtol/atol for "close" parity
    description: str = ""

    # -- lazy references ------------------------------------------------------

    @property
    def fn(self):
        return _resolve_ref(self.fn_ref)

    @property
    def oracle(self):
        return None if self.oracle_ref is None else _resolve_ref(self.oracle_ref)

    # -- tile space -----------------------------------------------------------

    @property
    def tunable_names(self) -> tuple:
        return tuple(p for p, _ in self.tunables)

    def bucket(self, **shape):
        """Shape bucket for ``shape`` -- the autotune cache key component."""
        if self.bucket_fn is None:
            return ()
        return self.bucket_fn(**shape)

    def default_tiles(self, bucket) -> dict:
        """Safe tiles for ``bucket``: the spec's defaults, shrunk to the
        largest admissible candidate when they violate the bucket constraint.
        Best-effort when the whole space is inadmissible (a bucket no tile
        satisfies, e.g. blocks that cannot fit VMEM at any k_tile): the raw
        defaults come back unchecked, and it is the *caller's* job to pick a
        different impl for such shapes (the engines' auto-selection does)."""
        tiles = dict(self.defaults_fn(bucket)) if self.defaults_fn else {}
        if tiles and self.constraint is not None and not self.constraint(bucket, tiles):
            cands = self.candidates(bucket)
            if cands:
                return cands[-1]
        return tiles

    def candidates(self, bucket) -> list[dict]:
        """Every admissible tile assignment for ``bucket`` (full product)."""
        combos: list[dict] = [{}]
        for param, values in self.tunables:
            combos = [{**c, param: v} for c in combos for v in values]
        if self.constraint is not None:
            combos = [c for c in combos if self.constraint(bucket, c)]
        return combos

    def cost_estimate(self, **shape) -> dict | None:
        return None if self.cost_fn is None else self.cost_fn(**shape)

    def compiler_params(self, **shape):
        """``pltpu.CompilerParams`` for ``shape`` (None without a params_fn)."""
        if self.params_fn is None:
            return None
        from jax.experimental.pallas import tpu as pltpu

        return pltpu.CompilerParams(**self.params_fn(**shape))


# ---------------------------------------------------------------------------
# The registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, KernelSpec] = {}


def register(spec: KernelSpec) -> KernelSpec:
    if spec.engine not in ENGINES:
        raise ValueError(f"unknown engine {spec.engine!r} (not in {ENGINES})")
    if spec.name in _REGISTRY:
        raise ValueError(f"kernel {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> KernelSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no kernel {name!r} registered (have {sorted(_REGISTRY)})"
        ) from None


def registered() -> tuple[KernelSpec, ...]:
    return tuple(_REGISTRY.values())


def specs_for(engine: str) -> tuple[KernelSpec, ...]:
    return tuple(s for s in _REGISTRY.values() if s.engine == engine)


def impl_names(engine: str) -> tuple[str, ...]:
    """The engine's impl menu, in registration (= preference-listing) order."""
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r} (not in {ENGINES})")
    return tuple(s.impl for s in _REGISTRY.values() if s.engine == engine)


def describe() -> str:
    """Human-readable registry listing (``operator_dse.py --kernel-impl list``)."""
    lines = []
    for engine in ENGINES:
        lines.append(f"{engine}:")
        for s in specs_for(engine):
            space = ", ".join(
                f"{p} in {list(v)}" for p, v in s.tunables
            ) or "no tunables"
            lines.append(f"  {s.impl:7s} {s.name:16s} {space}")
            if s.description:
                lines.append(f"          {s.description}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Registered specs
# ---------------------------------------------------------------------------
#
# All formulas below are pure host python over the shape bucket; anything that
# needs the operator model imports it lazily (numpy-only, no JAX).


# v5e's default scoped-VMEM limit: a kernel is never given less than the
# compiler would give it without a limit.
_SCOPED_VMEM = 16 << 20
# what one kernel's blocks and temporaries may occupy of a core's 128 MiB VMEM
_VMEM_FIT = 64 << 20


def _vmem_bytes(*shape: int, itemsize: int = 4) -> int:
    """VMEM bytes of one buffer of ``shape``: the minor dim pads to whole
    128-lane tiles and the second-minor to whole 8-sublane tiles."""
    *lead, sub, lane = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    n = 1
    for x in lead:
        n *= x
    return itemsize * n * (-(-sub // 8) * 8) * (-(-lane // 128) * 128)


def _vmem_limit(blocks: int, temps: int = 0) -> int:
    """Scoped-VMEM limit for double-buffered ``blocks`` plus ``temps``."""
    return max(_SCOPED_VMEM, 2 * blocks + temps)


def _char_bound(n_bits: int) -> int:
    from repro.core.operator_model import spec_for

    from_spec = spec_for(n_bits)
    row_mag = 1 << (from_spec.width - 1)
    approx = row_mag * ((4**from_spec.rows - 1) // 3)
    return approx + (1 << (2 * n_bits - 2))


def _char_bucket(*, n_bits: int, d: int):
    return (int(n_bits), _pow2_bucket(d, cap=1024))


def _char_constraint(bucket, tiles) -> bool:
    n_bits, d = bucket
    a = 1 << n_bits
    a_tile, d_block = tiles["a_tile"], tiles["d_block"]
    if a_tile > a or a % a_tile or d_block > d:
        return False
    # int32 safety: every per-tile partial sum must stay < 2^31 (the exact
    # int64 host combine depends on exactly-representable tile partials)
    return a_tile * a * _char_bound(n_bits) < (1 << 31)


def _char_defaults(bucket) -> dict:
    n_bits, d = bucket
    a = 1 << n_bits
    tile = a
    while tile > 1 and tile * a * _char_bound(n_bits) >= (1 << 30):
        tile //= 2
    return {"a_tile": tile, "d_block": min(8, d)}


def _char_cost(*, rows: int, d: int, a: int, b: int, a_tile: int, **_) -> dict:
    # per element of the (D, A, B) error table: R plane-selects + shift-adds,
    # the |e| decomposition and 6 reduction channels; outputs are the two
    # (A/a_tile, D, 8) partial stacks
    return {
        "flops": d * a * b * (6 * rows + 12),
        "bytes_accessed": 4 * (rows * d * 4 * b + 2 * a * b) + 8 * (a // a_tile) * d * 8,
        "transcendentals": 0,
    }


def _char_params(*, rows: int, d_block: int, a_tile: int, b: int, **_) -> dict:
    blocks = (rows * d_block * _vmem_bytes(4, b) + 2 * _vmem_bytes(a_tile, b)
              + 2 * _vmem_bytes(d_block, 8))
    # the per-row pair selectors plus one config's error-tile temporaries
    temps = (rows + 10) * _vmem_bytes(a_tile, b)
    return {
        # output blocks are disjoint across both grid axes
        "dimension_semantics": ("parallel", "parallel"),
        "vmem_limit_bytes": _vmem_limit(blocks, temps),
    }


def _entry_char_cost(*, rows: int, d: int, a: int, b: int, a_tile: int,
                     width: int, **_) -> dict:
    # the table-kernel reduction plus the in-VMEM synthesis: R*4 carry chains
    # of `width` steps (~6 lane-ops each) over the B axis, re-run per A tile;
    # HBM traffic is just the (D, R) masks and the partial stacks
    return {
        "flops": d * a * b * (6 * rows + 12)
        + (a // a_tile) * d * rows * 4 * b * width * 6,
        "bytes_accessed": 4 * d * rows + 8 * (a // a_tile) * d * 8,
        "transcendentals": 0,
    }


def _entry_char_params(*, rows: int, d_block: int, a_tile: int, b: int, **_) -> dict:
    # the masks block lives in SMEM; VMEM holds the two partial blocks, the
    # synthesized planes and one config's error-tile temporaries
    blocks = 2 * _vmem_bytes(d_block, 8)
    temps = (rows + 12) * _vmem_bytes(a_tile, b) + 8 * _vmem_bytes(1, b)
    return {
        "dimension_semantics": ("parallel", "parallel"),
        "vmem_limit_bytes": _vmem_limit(blocks, temps),
    }


def _app_bucket(*, n_bits: int, d: int, m: int, k: int, n: int):
    return (
        int(n_bits),
        _pow2_bucket(d, cap=1024),
        _pow2_bucket(m),
        _pow2_bucket(k),
        _pow2_bucket(n),
    )


def _gemv_blocks(*, m: int, k_tile: int, n: int) -> tuple[int, int]:
    """(VMEM blocks, temporaries) of one GEMV grid step shared by both app
    kernels: the A/B code tiles and the output block, plus the pair masks,
    the lane-gathered plane tile and the per-row partials."""
    blocks = (_vmem_bytes(m, k_tile) + _vmem_bytes(k_tile, n)
              + _vmem_bytes(m, n))
    temps = (2 * _vmem_bytes(m, k_tile) + 3 * _vmem_bytes(k_tile, 128)
             + 2 * _vmem_bytes(m, 128) + 3 * _vmem_bytes(m, n))
    return blocks, temps


def _gemv_tile_ok(bucket, k_tile: int) -> bool:
    _, _, _, k, _ = bucket
    # never wider than the padded K; k_tile is the lane dim of the A tile,
    # so it is whole 128-lane tiles or the whole (padded) K
    return k_tile <= k and (k_tile % 128 == 0 or k_tile == k)


def _row_table_bytes(n_bits: int) -> int:
    from repro.core.operator_model import spec_for

    spec = spec_for(n_bits)
    return 4 * 2 * 4 * spec.n_inputs * spec.n_row_masks


def _app_constraint(bucket, tiles) -> bool:
    n_bits, d, m, k, n = bucket
    k_tile = tiles["k_tile"]
    if not _gemv_tile_ok(bucket, k_tile):
        return False
    # the per-row planes are gathered from the RowTables constant, which
    # grows as 2^(2N+4) bytes: 4 MiB at 8 bits, 1 GiB at 12
    if _row_table_bytes(n_bits) > _VMEM_FIT:
        return False
    rows = -(-n_bits // 2)
    blocks, temps = _gemv_blocks(m=m, k_tile=k_tile, n=n)
    blocks += rows * _vmem_bytes(4, 1 << n_bits)
    return 2 * blocks + temps < _VMEM_FIT


def _app_xla_constraint(bucket, tiles) -> bool:
    # chunks wider than the config batch degenerate to d (min() in the
    # engine), so they would duplicate the d-sized candidate
    return tiles["d_chunk"] <= bucket[1]


def _entry_app_constraint(bucket, tiles) -> bool:
    n_bits, d, m, k, n = bucket
    k_tile = tiles["k_tile"]
    if not _gemv_tile_ok(bucket, k_tile):
        return False
    # VMEM fit: one row's synthesized planes + the GEMV tiles -- no row
    # tables, which is what admits 12-bit operands the table kernel cannot
    # stage
    blocks, temps = _gemv_blocks(m=m, k_tile=k_tile, n=n)
    temps += 8 * _vmem_bytes(1, 1 << n_bits)
    return 2 * blocks + temps < _VMEM_FIT


def _entry_app_cost(*, d: int, m: int, k: int, n: int, a: int, rows: int,
                    width: int, **_) -> dict:
    return {
        # the table kernel's R*4 mask GEMMs + the per-grid-step synthesis
        # (R*4 chains of `width` steps over the B axis; one grid step per
        # default-width K tile)
        "flops": 8 * d * m * k * n * rows
        + d * max(1, k // 128) * rows * 4 * a * width * 6,
        "bytes_accessed": 4 * (d * rows + m * k + k * n + d * m * n),
        "transcendentals": 0,
    }


def _entry_app_params(*, m: int, k_tile: int, n: int, a: int, **_) -> dict:
    blocks, temps = _gemv_blocks(m=m, k_tile=k_tile, n=n)
    return {
        "dimension_semantics": ("parallel", "arbitrary"),
        "vmem_limit_bytes": _vmem_limit(blocks, temps + 8 * _vmem_bytes(1, a)),
    }


def _app_defaults(bucket) -> dict:
    _, _, _, k, _ = bucket
    return {"k_tile": min(128, _pow2_bucket(k))}


def _app_xla_defaults(bucket) -> dict:
    return {"d_chunk": min(8, bucket[1])}


def _app_cost(*, d: int, m: int, k: int, n: int, a: int, rows: int, **_) -> dict:
    return {
        # R*4 (M, K) x (K, N) mask GEMMs per config
        "flops": 8 * d * m * k * n * rows,
        "bytes_accessed": 4 * (d * rows * 4 * a + m * k + k * n + d * m * n),
        "transcendentals": 0,
    }


def _app_params(*, m: int, k_tile: int, n: int, a: int, rows: int, **_) -> dict:
    blocks, temps = _gemv_blocks(m=m, k_tile=k_tile, n=n)
    return {
        # the k axis accumulates into a revisited output block: sequential
        "dimension_semantics": ("parallel", "arbitrary"),
        "vmem_limit_bytes": _vmem_limit(blocks + rows * _vmem_bytes(4, a), temps),
    }


def _axo_bucket(*, m: int, k: int, n: int, rank: int):
    return (
        _pow2_bucket(m),
        _pow2_bucket(k),
        _pow2_bucket(n),
        _pow2_bucket(rank, cap=64),
    )


def _axo_constraint(bucket, tiles) -> bool:
    m, k, n, rank = bucket
    bm, bn, bk = tiles["bm"], tiles["bn"], tiles["bk"]
    # blocks never exceed the padded problem (the kernel pads M to sublane
    # multiples of 8 and K/N to lane multiples of 128, then to the block)
    if bm > max(8, m) or bn > max(128, n) or bk > max(128, k):
        return False
    # VMEM fit: a/b value blocks + the rank-stacked factor blocks + f32
    # accumulator scratch and output block
    return 2 * _axo_blocks(bm=bm, bn=bn, bk=bk, rank=rank) < _VMEM_FIT


def _axo_defaults(bucket) -> dict:
    m, _, _, _ = bucket
    return {"bm": min(128, max(8, m)), "bn": 128, "bk": 128}


def _axo_cost(*, m: int, k: int, n: int, rank: int, **_) -> dict:
    return {
        # the exact product plus one (bm, bk) x (bk, bn) matmul per rank term
        "flops": 2 * m * n * k * (1 + rank),
        "bytes_accessed": 4 * ((1 + rank) * (m * k + k * n) + m * n),
        "transcendentals": 0,
    }


def _axo_blocks(*, bm: int, bn: int, bk: int, rank: int) -> int:
    return ((1 + rank) * (_vmem_bytes(bm, bk) + _vmem_bytes(bk, bn))
            + _vmem_bytes(bm, bn))


def _axo_params(*, bm: int, bn: int, bk: int, rank: int, **_) -> dict:
    # + the accumulator scratch and the step's (bm, bn) partial products
    temps = 3 * _vmem_bytes(bm, bn)
    return {
        # the K axis accumulates into a revisited output block: sequential
        "dimension_semantics": ("parallel", "parallel", "arbitrary"),
        "vmem_limit_bytes": _vmem_limit(
            _axo_blocks(bm=bm, bn=bn, bk=bk, rank=rank), temps),
    }


def _flash_bucket(*, sq: int, skv: int, hd: int):
    return (_pow2_bucket(sq), _pow2_bucket(skv), _pow2_bucket(hd, cap=256))


def _flash_constraint(bucket, tiles) -> bool:
    sq, skv, hd = bucket
    bq, bk = tiles["bq"], tiles["bk"]
    if bq > max(8, sq) or bk > max(128, skv):
        return False
    # q/acc/o blocks + k/v blocks + the (bq, bk) score matrix and m/l rows
    blocks, temps = _flash_blocks(bq=bq, bk=bk, hd=hd)
    return 2 * blocks + temps < _VMEM_FIT


def _flash_defaults(bucket) -> dict:
    sq, _, _ = bucket
    return {"bq": min(128, max(8, sq)), "bk": 128}


def _flash_cost(*, b: int, h: int, sq: int, skv: int, hd: int,
                causal: bool = True, **_) -> dict:
    pairs = b * h * sq * skv // (2 if causal else 1)
    return {
        "flops": 4 * pairs * hd,  # qk^T and pv, 2 flops/MAC each
        "bytes_accessed": 4 * (2 * b * h * sq * hd + 2 * b * h * skv * hd),
        "transcendentals": pairs,  # one exp per unmasked score
    }


def _flash_blocks(*, bq: int, bk: int, hd: int) -> tuple[int, int]:
    """(q/k/v/o blocks, m/l/acc scratch + score temporaries)."""
    blocks = 2 * _vmem_bytes(bq, hd) + 2 * _vmem_bytes(bk, hd)
    temps = (2 * _vmem_bytes(bq) + _vmem_bytes(bq, hd)
             + 3 * _vmem_bytes(bq, bk))
    return blocks, temps


def _flash_params(*, bq: int, bk: int, hd: int, **_) -> dict:
    return {
        # KV blocks revisit the q block's scratch (online softmax): sequential
        "dimension_semantics": ("parallel", "parallel", "parallel", "arbitrary"),
        "vmem_limit_bytes": _vmem_limit(*_flash_blocks(bq=bq, bk=bk, hd=hd)),
    }


def _moo_bucket(*, p: int, n_obj: int):
    return (_pow2_bucket(p), int(n_obj))


def _moo_constraint(bucket, tiles) -> bool:
    p, _ = bucket
    tile, j_tile = tiles["tile"], tiles["j_tile"]
    # j_tile is the lane dim of the dominator rows: whole 128-lane tiles or
    # the whole (padded) population
    return tile <= p and j_tile <= p and (j_tile % 128 == 0 or j_tile == p)


def _moo_defaults(bucket) -> dict:
    p, _ = bucket
    # the 2-D-friendly layout: j (dominator) tiles sized to the 128 lanes
    return {"tile": min(64, p), "j_tile": min(128, p)}


def _moo_cost(*, p: int, n_obj: int, **_) -> dict:
    return {
        "flops": p * p * (4 * n_obj + 8),
        "bytes_accessed": 4 * (2 * p * n_obj + 4 * p),
        "transcendentals": 0,
    }


def _moo_params(*, tile: int, j_tile: int, n_obj: int, **_) -> dict:
    # i columns (padded to 128 lanes), j rows (padded to 8 sublanes), output
    blocks = (_vmem_bytes(tile, n_obj) + 2 * _vmem_bytes(tile, 1)
              + _vmem_bytes(n_obj, j_tile) + 2 * _vmem_bytes(1, j_tile))
    # the (tile, j_tile) comparison masks
    temps = (2 * n_obj + 6) * _vmem_bytes(tile, j_tile)
    return {
        # j revisits the output block (accumulation): sequential
        "dimension_semantics": ("parallel", "arbitrary"),
        "vmem_limit_bytes": _vmem_limit(blocks, temps),
    }


# -- fastchar: BEHAV characterization partials ------------------------------

register(KernelSpec(
    name="fastchar.xla",
    engine="fastchar",
    impl="xla",
    fn_ref="repro.kernels.tuning:_run_fastchar",
    oracle_ref="repro.kernels.tuning:_oracle_fastchar",
    tunables=(
        ("a_tile", (8, 16, 32, 64, 128, 256)),
        ("d_block", (2, 4, 8, 16, 32)),
    ),
    defaults_fn=_char_defaults,
    bucket_fn=_char_bucket,
    constraint=_char_constraint,
    description="lax.map-chunked XLA twin of the Pallas BEHAV reduction",
))

register(KernelSpec(
    name="fastchar.pallas",
    engine="fastchar",
    impl="pallas",
    fn_ref="repro.kernels.tuning:_run_fastchar",
    oracle_ref="repro.kernels.tuning:_oracle_fastchar",
    tunables=(
        ("a_tile", (8, 16, 32, 64, 128, 256)),
        ("d_block", (2, 4, 8, 16, 32)),
    ),
    defaults_fn=_char_defaults,
    bucket_fn=_char_bucket,
    constraint=_char_constraint,
    cost_fn=_char_cost,
    params_fn=_char_params,
    description="tiled error-table reconstruction + per-A-tile partial stats",
))

register(KernelSpec(
    name="fastchar.entry",
    engine="fastchar",
    impl="entry",
    fn_ref="repro.kernels.tuning:_run_fastchar",
    oracle_ref="repro.kernels.tuning:_oracle_fastchar",
    tunables=(
        ("a_tile", (8, 16, 32, 64, 128, 256)),
        ("d_block", (2, 4, 8, 16, 32)),
    ),
    defaults_fn=_char_defaults,
    bucket_fn=_char_bucket,
    constraint=_char_constraint,
    description="table-free XLA twin: per-row planes synthesized from masks",
))

register(KernelSpec(
    name="fastchar.entry_pallas",
    engine="fastchar",
    impl="entry_pallas",
    fn_ref="repro.kernels.tuning:_run_fastchar",
    oracle_ref="repro.kernels.tuning:_oracle_fastchar",
    tunables=(
        ("a_tile", (8, 16, 32, 64, 128, 256)),
        ("d_block", (2, 4, 8, 16, 32)),
    ),
    defaults_fn=_char_defaults,
    bucket_fn=_char_bucket,
    constraint=_char_constraint,
    cost_fn=_entry_char_cost,
    params_fn=_entry_char_params,
    description="table-free BEHAV kernel: masks-only input, in-VMEM synthesis",
))

# -- fastapp: table arithmetic ----------------------------------------------

register(KernelSpec(
    name="fastapp.gemm",
    engine="fastapp",
    impl="gemm",
    fn_ref="repro.kernels.tuning:_run_fastapp",
    oracle_ref="repro.kernels.tuning:_oracle_fastapp",
    tunables=(),
    bucket_fn=_app_bucket,
    description="pair-plane masked f32 GEMMs over the tiny per-row tables",
))

register(KernelSpec(
    name="fastapp.xla",
    engine="fastapp",
    impl="xla",
    fn_ref="repro.kernels.tuning:_run_fastapp",
    oracle_ref="repro.kernels.tuning:_oracle_fastapp",
    tunables=(("d_chunk", (2, 4, 8, 16, 32)),),
    defaults_fn=_app_xla_defaults,
    bucket_fn=_app_bucket,
    constraint=_app_xla_constraint,
    description="flattened jnp.take gathers tiled by lax.map config chunks",
))

register(KernelSpec(
    name="fastapp.pallas",
    engine="fastapp",
    impl="pallas",
    fn_ref="repro.kernels.tuning:_run_fastapp",
    oracle_ref="repro.kernels.tuning:_oracle_fastapp",
    # whole 128-lane tiles (_gemv_tile_ok); a K < 128 bucket has no
    # candidates and runs its default, the whole K
    tunables=(("k_tile", (128, 256)),),
    defaults_fn=_app_defaults,
    bucket_fn=_app_bucket,
    constraint=_app_constraint,
    cost_fn=_app_cost,
    params_fn=_app_params,
    description="K-tiled batched table-GEMV, per-config table VMEM-resident",
))

register(KernelSpec(
    name="fastapp.entry",
    engine="fastapp",
    impl="entry",
    fn_ref="repro.kernels.tuning:_run_fastapp",
    oracle_ref="repro.kernels.tuning:_oracle_fastapp",
    tunables=(("d_chunk", (2, 4, 8, 16, 32)),),
    defaults_fn=_app_xla_defaults,
    bucket_fn=_app_bucket,
    constraint=_app_xla_constraint,
    description="table-free gathers from device-synthesized per-row planes",
))

register(KernelSpec(
    name="fastapp.entry_pallas",
    engine="fastapp",
    impl="entry_pallas",
    fn_ref="repro.kernels.tuning:_run_fastapp",
    oracle_ref="repro.kernels.tuning:_oracle_fastapp",
    tunables=(("k_tile", (128, 256)),),
    defaults_fn=_app_defaults,
    bucket_fn=_app_bucket,
    constraint=_entry_app_constraint,
    cost_fn=_entry_app_cost,
    params_fn=_entry_app_params,
    description="table-free K-tiled GEMV: VMEM tile synthesized from masks",
))

# -- axo_matmul: AxO serving matmul (exact product + rank-R error factors) --

register(KernelSpec(
    name="axo_matmul.xla",
    engine="axo_matmul",
    impl="xla",
    fn_ref="repro.kernels.tuning:_run_axo",
    oracle_ref="repro.kernels.tuning:_oracle_axo",
    tunables=(),
    bucket_fn=_axo_bucket,
    tol=1e-5,
    description="ref_axo_matmul_lowrank: einsum exact product + rank terms",
))

register(KernelSpec(
    name="axo_matmul.pallas",
    engine="axo_matmul",
    impl="pallas",
    fn_ref="repro.kernels.tuning:_run_axo",
    oracle_ref="repro.kernels.tuning:_oracle_axo",
    tunables=(
        ("bm", (8, 16, 32, 64, 128, 256)),
        ("bn", (128, 256)),
        ("bk", (128, 256)),
    ),
    defaults_fn=_axo_defaults,
    bucket_fn=_axo_bucket,
    constraint=_axo_constraint,
    cost_fn=_axo_cost,
    params_fn=_axo_params,
    tol=1e-5,
    description="K-blocked AxO matmul, rank terms unrolled in VMEM scratch",
))

# -- flash_attention: serving attention -------------------------------------

register(KernelSpec(
    name="flash_attention.xla",
    engine="flash_attention",
    impl="xla",
    fn_ref="repro.kernels.tuning:_run_flash",
    oracle_ref="repro.kernels.tuning:_oracle_flash",
    tunables=(),
    bucket_fn=_flash_bucket,
    tol=5e-6,
    description="ref_flash_attention: materialized-score softmax attention",
))

register(KernelSpec(
    name="flash_attention.pallas",
    engine="flash_attention",
    impl="pallas",
    fn_ref="repro.kernels.tuning:_run_flash",
    oracle_ref="repro.kernels.tuning:_oracle_flash",
    tunables=(
        ("bq", (8, 16, 32, 64, 128, 256)),
        ("bk", (128, 256, 512)),
    ),
    defaults_fn=_flash_defaults,
    bucket_fn=_flash_bucket,
    constraint=_flash_constraint,
    cost_fn=_flash_cost,
    params_fn=_flash_params,
    tol=5e-6,
    description="online-softmax GQA attention, KV-blocked with m/l scratch",
))

# -- fastmoo: dominance counts ----------------------------------------------

register(KernelSpec(
    name="fastmoo.xla",
    engine="fastmoo",
    impl="xla",
    fn_ref="repro.kernels.tuning:_run_fastmoo",
    oracle_ref="repro.kernels.tuning:_oracle_fastmoo",
    tunables=(),
    bucket_fn=_moo_bucket,
    description="(P, P, n_obj) dominance-matrix counts (masked column sums)",
))

register(KernelSpec(
    name="fastmoo.pallas",
    engine="fastmoo",
    impl="pallas",
    fn_ref="repro.kernels.tuning:_run_fastmoo",
    oracle_ref="repro.kernels.tuning:_oracle_fastmoo",
    tunables=(
        ("tile", (8, 16, 32, 64, 128)),
        ("j_tile", (8, 16, 32, 64, 128)),
    ),
    defaults_fn=_moo_defaults,
    bucket_fn=_moo_bucket,
    constraint=_moo_constraint,
    cost_fn=_moo_cost,
    params_fn=_moo_params,
    description="tiled dominance counts, 2-D-friendly (tile, j_tile) blocks",
))
