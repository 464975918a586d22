"""Pallas reduction kernel for batched BEHAV characterization (fastchar backend).

The AxOMaP bottleneck is turning thousands of LUT configs into error statistics:
the numpy oracle materializes a ``(D, 2^N, 2^N)`` float64 error table per batch
(134 MB per 256-config batch at N=8) and reduces it on the host.  This kernel
computes the same statistics *without ever materializing the error tables in
HBM*: each grid step reconstructs one ``(Db, Ta, B)`` error-table tile in VMEM
from the tiny per-row config tables and reduces it to per-config partial sums.

Inputs (see ``repro.core.fastchar`` for how they are built):

  small: (R, D, 4, B) int32 -- per-row outputs ``V_r`` of config ``d`` for each
         of the 4 values of the row's multiplier bit-pair, for every B operand.
         This is the result of the vectorized ``jnp.take`` over ``RowTables``;
         it is ~4096 ints per config vs 65536 for the full table.
  exact: (A, B) int32 -- exact signed product table.
  w:     (A, B) f32   -- 1 / max(|exact|, 1), the relative-error weights.

The approximate product of config ``d`` for operand codes ``(a, b)`` is

    P[d, a, b] = sum_r small[r, d, pair_r(a), b] << 2r

where ``pair_r(a) = 2*bit_{2r}(a) + bit_{2r+1}(a)`` selects one of 4 planes.
Plane selection is done with broadcast ``where`` masks over an iota of the A
tile -- no gathers inside the kernel, pure VPU work.

Outputs are *per-A-tile partial* statistics so every integer channel stays
exactly representable in int32 (the host combines tiles in int64 -- that is
what makes four of the five BEHAV metrics bit-identical to the float64 numpy
oracle).  Channels of the (n_ta, D, 8) outputs:

  int32: 0 sum|e|   1 count(e != 0)   2 max|e|
         3 sum hi^2  4 sum hi*lo  5 sum lo^2    (hi = |e| >> 8, lo = |e| & 255,
                                                 so e^2 = 65536*h2 + 512*hl + l2)
  f32:   0 sum |e| * w   (relative error; f32 rounding, combined in f64)

Tile-size rule: callers must pick ``a_tile`` such that
``a_tile * B * max|e| < 2^31`` (see ``fastchar.max_abs_error_bound``).

Block shapes come from the kernel registry (``kernels.registry``, spec
``"fastchar.pallas"``): passing ``a_tile``/``d_block`` as ``None`` resolves
the registry's int32-safe defaults, and contexts with ``tuning != "off"``
hand tuned tiles down through ``fastchar.behav_partials``.  The registry also
supplies the ``pl.CostEstimate`` and TPU compiler params (both grid axes are
``parallel`` -- every (i, j) step owns a disjoint output block -- and the
VMEM limit is sized to double-buffered blocks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry
from ..core.operator_model import _chain_eval, spec_for

__all__ = ["behav_stats_pallas", "behav_stats_entry_pallas", "N_CHAN"]

N_CHAN = 8  # output channel count (padded for lane alignment)


def _channel_rows(err, w):
    """Reduce one config's (Ta, B) error tile to its (1, N_CHAN) partial rows.

    Every reduction keeps rank 2 ((Ta, B) -> (1, 1)) and the channels are
    placed with lane selects, not a stack of rank-1 values: Mosaic lays out
    only rank >= 2 values here.
    """
    abs_e = jnp.abs(err)
    hi = abs_e >> 8
    lo = abs_e & 255

    def total(x):
        return x.sum(axis=1, keepdims=True).sum(axis=0, keepdims=True)

    chans = (
        total(abs_e),
        total((err != 0).astype(jnp.int32)),
        abs_e.max(axis=1, keepdims=True).max(axis=0, keepdims=True),
        total(hi * hi),
        total(hi * lo),
        total(lo * lo),
    )
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, N_CHAN), 1)
    int_row = jnp.zeros((1, N_CHAN), jnp.int32)
    for c, v in enumerate(chans):
        int_row = jnp.where(lane == c, v, int_row)
    rel = total(abs_e.astype(jnp.float32) * w)
    rel_row = jnp.where(lane == 0, rel, jnp.zeros((1, N_CHAN), jnp.float32))
    return int_row, rel_row


def _per_config(d_block: int, int_ref, rel_ref, rows_fn):
    """Run ``rows_fn(dd) -> (int_row, rel_row)`` over the block's configs and
    store the stacked (1, Db, N_CHAN) partials once."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (d_block, N_CHAN), 0)

    def body(dd, carry):
        acc_i, acc_f = carry
        int_row, rel_row = rows_fn(dd)
        return (jnp.where(sub == dd, int_row, acc_i),
                jnp.where(sub == dd, rel_row, acc_f))

    acc_i, acc_f = jax.lax.fori_loop(
        0, d_block, body,
        (jnp.zeros((d_block, N_CHAN), jnp.int32),
         jnp.zeros((d_block, N_CHAN), jnp.float32)),
    )
    int_ref[...] = acc_i[None]
    rel_ref[...] = acc_f[None]


def _kernel(small_ref, exact_ref, w_ref, int_ref, rel_ref, *, rows: int,
            a_tile: int, d_block: int):
    """One (d_block, a_tile) step: rebuild each config's error tile, reduce."""
    j = pl.program_id(1)
    b = exact_ref.shape[-1]

    # Absolute A codes covered by this tile, broadcast over the B axis.
    a_ids = jax.lax.broadcasted_iota(jnp.int32, (a_tile, b), 0) + j * a_tile
    pairs = [2 * ((a_ids >> (2 * r)) & 1) + ((a_ids >> (2 * r + 1)) & 1)
             for r in range(rows)]
    exact = exact_ref[...]
    w = w_ref[...]

    def rows_fn(dd):
        approx = None
        for r in range(rows):  # static unroll over partial-product rows
            acc = None
            for p in range(4):  # select one of 4 bit-pair planes, no gathers
                plane = small_ref[r, dd, pl.ds(p, 1), :]          # (1, B)
                term = jnp.where(pairs[r] == p, plane, 0)
                acc = term if acc is None else acc + term
            shifted = acc << (2 * r)
            approx = shifted if approx is None else approx + shifted
        return _channel_rows(approx - exact, w)                   # (Ta, B) err

    _per_config(d_block, int_ref, rel_ref, rows_fn)


@functools.partial(jax.jit, static_argnames=("d_block", "a_tile", "interpret"))
def behav_stats_pallas(
    small: jnp.ndarray,           # (R, D, 4, B) int32
    exact: jnp.ndarray,           # (A, B) int32
    w: jnp.ndarray,               # (A, B) f32
    d_block: int | None = None,
    a_tile: int | None = None,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Tiled BEHAV partial statistics; returns (int_partials, rel_partials).

    Shapes: (A // a_tile, D, N_CHAN) int32 and float32.  D must divide by
    ``d_block`` and A by ``a_tile`` (``fastchar`` pads the config batch).
    ``None`` tiles resolve the registry defaults for this shape bucket.
    """
    rows, d, four, b = small.shape
    a = exact.shape[0]
    spec = registry.get("fastchar.pallas")
    if d_block is None or a_tile is None:
        tiles = spec.default_tiles(spec.bucket(n_bits=a.bit_length() - 1, d=d))
        d_block = tiles["d_block"] if d_block is None else d_block
        a_tile = tiles["a_tile"] if a_tile is None else a_tile
    assert four == 4 and exact.shape == (a, b) and w.shape == (a, b)
    assert d % d_block == 0, (d, d_block)
    assert a % a_tile == 0, (a, a_tile)
    n_ta = a // a_tile

    cost = spec.cost_estimate(rows=rows, d=d, a=a, b=b, a_tile=a_tile)
    params = spec.compiler_params(rows=rows, d_block=d_block, a_tile=a_tile, b=b)
    grid = (d // d_block, n_ta)
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows, a_tile=a_tile, d_block=d_block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, d_block, 4, b), lambda i, j: (0, i, 0, 0)),
            pl.BlockSpec((a_tile, b), lambda i, j: (j, 0)),
            pl.BlockSpec((a_tile, b), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, d_block, N_CHAN), lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, d_block, N_CHAN), lambda i, j: (j, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_ta, d, N_CHAN), jnp.int32),
            jax.ShapeDtypeStruct((n_ta, d, N_CHAN), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(**cost),
        compiler_params=params,
        interpret=interpret,
    )(small, exact, w)


# ---------------------------------------------------------------------------
# Table-free variant: reconstruct the tile from the (D, R) config masks
# ---------------------------------------------------------------------------


def _entry_kernel(masks_ref, int_ref, rel_ref, *, n_bits: int, a_tile: int,
                  d_block: int):
    """One (d_block, a_tile) step with NO table inputs: the per-row planes are
    synthesized in VMEM from the config masks by the carry-chain model
    (``R * 4 * W`` chain steps over the B axis), the exact products and
    relative-error weights from an iota.  The only HBM traffic besides the
    outputs is the (d_block, R) masks block, read as scalars from SMEM --
    ~4096x less than the ``small``+``exact``+``w`` inputs of the table
    kernel."""
    spec = spec_for(n_bits)
    j = pl.program_id(1)
    b = spec.n_inputs
    half = b // 2
    w_bits, cpr = spec.width, spec.cols_removable
    modw = (1 << w_bits) - 1

    b_codes = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    b_s = jnp.where(b_codes >= half, b_codes - b, b_codes)    # (1, B) signed

    a_ids = jax.lax.broadcasted_iota(jnp.int32, (a_tile, b), 0) + j * a_tile
    b_ids = jax.lax.broadcasted_iota(jnp.int32, (a_tile, b), 1)
    a_sv = jnp.where(a_ids >= half, a_ids - b, a_ids)
    b_sv = jnp.where(b_ids >= half, b_ids - b, b_ids)
    exact = a_sv * b_sv                                       # (Ta, B) int32
    w = 1.0 / jnp.maximum(jnp.abs(exact), 1).astype(jnp.float32)
    pairs = [2 * ((a_ids >> (2 * r)) & 1) + ((a_ids >> (2 * r + 1)) & 1)
             for r in range(spec.rows)]

    def rows_fn(dd):
        approx = None
        for r in range(spec.rows):  # static unroll over partial-product rows
            top = r == spec.rows - 1
            mask_r = masks_ref[dd, r]                         # SMEM scalar
            bx = -b_s if top else b_s
            acc = None
            for p in range(4):  # synthesize the bit-pair plane, then select it
                a0, a1 = (p >> 1) & 1, p & 1
                t1 = (b_s & modw) if a0 else jnp.zeros_like(b_s)
                t2 = ((bx << 1) & modw) if a1 else jnp.zeros_like(b_s)
                plane = _chain_eval(t1, t2, mask_r, w_bits, cpr, jnp, jnp.int32)
                term = jnp.where(pairs[r] == p, plane, 0)     # (Ta, B)
                acc = term if acc is None else acc + term
            shifted = acc << (2 * r)
            approx = shifted if approx is None else approx + shifted
        return _channel_rows(approx - exact, w)

    _per_config(d_block, int_ref, rel_ref, rows_fn)


@functools.partial(jax.jit, static_argnames=("n_bits", "d_block", "a_tile", "interpret"))
def behav_stats_entry_pallas(
    masks: jnp.ndarray,           # (D, R) int32 per-row config masks
    n_bits: int,
    d_block: int | None = None,
    a_tile: int | None = None,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Table-free twin of :func:`behav_stats_pallas`; same outputs/channels.

    Integer channels are bit-identical to the table kernel (the synthesized
    planes equal the gathered ones); the relative channel divides in f32
    in-kernel instead of staging f64-rounded reciprocals, which agrees with
    the oracle to ~1e-7 relative.  Signed multipliers only.
    """
    op_spec = spec_for(n_bits)
    d, rows = masks.shape
    assert rows == op_spec.rows, (rows, op_spec.rows)
    a = b = op_spec.n_inputs
    spec = registry.get("fastchar.entry_pallas")
    if d_block is None or a_tile is None:
        tiles = spec.default_tiles(spec.bucket(n_bits=n_bits, d=d))
        d_block = tiles["d_block"] if d_block is None else d_block
        a_tile = tiles["a_tile"] if a_tile is None else a_tile
    assert d % d_block == 0, (d, d_block)
    assert a % a_tile == 0, (a, a_tile)
    n_ta = a // a_tile

    cost = spec.cost_estimate(rows=rows, d=d, a=a, b=b, a_tile=a_tile,
                              width=op_spec.width)
    params = spec.compiler_params(rows=rows, d_block=d_block, a_tile=a_tile, b=b)
    grid = (d // d_block, n_ta)
    return pl.pallas_call(
        functools.partial(_entry_kernel, n_bits=n_bits, a_tile=a_tile,
                          d_block=d_block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d_block, rows), lambda i, j: (i, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, d_block, N_CHAN), lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, d_block, N_CHAN), lambda i, j: (j, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_ta, d, N_CHAN), jnp.int32),
            jax.ShapeDtypeStruct((n_ta, d, N_CHAN), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(**cost),
        compiler_params=params,
        interpret=interpret,
    )(masks)
