"""Pallas batched table-GEMV kernels for the application-BEHAV engine (fastapp).

Application BEHAV turns a batch of approximate-operator product tables into
app-level quality metrics; its hot loop is integer matmul where every multiply
is a table lookup: ``out[d, m, n] = sum_k T_d[a[m, k], b[k, n]]``.  The XLA
path in :mod:`repro.apps.fastapp` gathers a ``(Dc, M, K, N)`` product tensor
per config chunk; these kernels never materialize the product tensor in HBM.

A TPU core cannot gather from a 2-D table, so both kernels use the per-row
decomposition of the product (the one ``char_kernels`` uses):

    T_d[a, b] = sum_r S_d[r, pair_r(a), b] << 2r

with ``pair_r(a) = 2*bit_{2r}(a) + bit_{2r+1}(a)``.  Then

    out[d] = sum_r 4^r sum_p [pair_r(A) == p] @ G_{d,r,p},
    G_{d,r,p}[k, n] = S_d[r, p, b[k, n]]

The lookup ``G`` indexes one ``(B,)`` plane with the weight codes: a lane
gather within 128-lane chunks of the plane (``jnp.take_along_axis`` on
``(k_tile, 128)`` operands, which Mosaic lowers to an in-register gather).
The contraction over K is an MXU matmul of the 0/1 pair masks with ``G``
at full f32 precision: every operand and partial sum is an integer below
2^24 (``|S| < 2^(N+1)``, ``k_tile <= 256``), so each row's f32 result is
exact and is combined in int32.

Grid: ``(D, K // k_tile)``; step ``(d, k)`` loads the config's planes (or
its ``R`` masks, from which the entry kernel synthesizes the planes in
VMEM), the ``(M, k_tile)`` operand-A tile and the ``(k_tile, Np)`` weight
tile, and accumulates the ``(1, M, Np)`` output block over k
(``@pl.when(k == 0)`` init).  Weight columns are padded to whole 128-lane
chunks by the wrapper and sliced off after.

Callers must pad K to a multiple of ``k_tile`` with zero codes: code 0 is the
operand value 0 and every config's table maps (0, 0) -> 0, so padding
contributes nothing to the sums (asserted in tests).  ``k_tile`` must be a
multiple of 128 or cover the whole (padded) K, because it is the lane
dimension of the A tile; the registry constraint enforces that.

``k_tile`` comes from the kernel registry (specs ``"fastapp.pallas"`` /
``"fastapp.entry_pallas"``): ``None`` resolves the registry default for the
(M, K, N) shape bucket, and a context with ``tuning != "off"`` hands tuned
tiles down through ``fastapp.table_matmul_jax``.  The registry also supplies
the ``pl.CostEstimate`` and compiler params -- the D axis is ``parallel``,
the K axis ``arbitrary`` (it accumulates into a revisited output block).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry
from ..core.operator_model import _chain_eval, spec_for

__all__ = ["table_gemv_pallas", "entry_gemv_pallas"]

LANES = 128


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _lookup(plane, b):
    """``plane[b]`` for a (1, Bp) plane and (kt, 128) codes, Bp % 128 == 0:
    one in-register lane gather per 128-entry chunk of the plane."""
    lo = b & (LANES - 1)
    chunk = b // LANES
    out = None
    for c in range(plane.shape[1] // LANES):
        src = jnp.broadcast_to(plane[:, c * LANES:(c + 1) * LANES], b.shape)
        got = jnp.take_along_axis(src, lo, axis=1)
        out = got if out is None else jnp.where(chunk == c, got, out)
    return out


def _gemv_step(planes_fn, rows: int, a_ref, b_ref, out_ref):
    """One (d, k) grid step; ``planes_fn(r)`` gives row r's four (1, Bp)
    planes.  Accumulates the (1, M, Np) int32 output block over k."""
    k = pl.program_id(1)
    a = a_ref[...]                                             # (M, kt)
    n_chunks = b_ref.shape[1] // LANES
    part = None
    for r in range(rows):  # static unroll over partial-product rows
        pair = 2 * ((a >> (2 * r)) & 1) + ((a >> (2 * r + 1)) & 1)
        planes = planes_fn(r)
        cols = []
        for c in range(n_chunks):
            b = b_ref[:, c * LANES:(c + 1) * LANES]           # (kt, 128)
            acc = None
            for p in range(4):
                sel = (pair == p).astype(jnp.float32)          # (M, kt)
                g = _lookup(planes[p], b).astype(jnp.float32)  # (kt, 128)
                dot = jnp.dot(sel, g, preferred_element_type=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST)
                acc = dot if acc is None else acc + dot
            cols.append(acc)
        row = cols[0] if n_chunks == 1 else jnp.concatenate(cols, axis=1)
        term = row.astype(jnp.int32) << (2 * r)                # (M, Np)
        part = term if part is None else part + term
    part = part[None]

    @pl.when(k == 0)
    def _init():
        out_ref[...] = part

    @pl.when(k > 0)
    def _acc():
        out_ref[...] += part


def _table_kernel(small_ref, a_ref, b_ref, out_ref, *, rows: int):
    sub = jax.lax.broadcasted_iota(jnp.int32, small_ref.shape[2:], 0)

    def planes_fn(r):
        # a masked sublane sum, not a one-row load: Mosaic cannot broadcast
        # a row loaded from the (4, B) tile
        blk = small_ref[r, 0]                                  # (4, Bp)
        return [jnp.where(sub == p, blk, 0).sum(axis=0, keepdims=True)
                for p in range(4)]

    _gemv_step(planes_fn, rows, a_ref, b_ref, out_ref)


def _entry_kernel(masks_ref, a_ref, b_ref, out_ref, *, n_bits: int, b_pad: int):
    """Table-free step: row r's four planes are synthesized in VMEM from the
    config's SMEM masks by the carry-chain model (``R * 4 * W`` chain steps
    over the B axis), so nothing per config but R ints is read from HBM.
    That is what admits wide operands whose row tables cannot be staged (a
    12-bit row-table constant is 1 GiB)."""
    spec = spec_for(n_bits)
    b_in = spec.n_inputs
    half = b_in // 2
    w_bits, cpr = spec.width, spec.cols_removable
    modw = (1 << w_bits) - 1
    # codes >= B (lane padding) synthesize junk that no weight code selects
    b_codes = jax.lax.broadcasted_iota(jnp.int32, (1, b_pad), 1)
    b_s = jnp.where(b_codes >= half, b_codes - b_in, b_codes)  # (1, Bp) signed

    row = pl.program_id(0) % masks_ref.shape[0]

    def planes_fn(r):
        mask_r = masks_ref[row, r]                             # SMEM scalar
        bx = -b_s if r == spec.rows - 1 else b_s
        planes = []
        for p in range(4):
            a0, a1 = (p >> 1) & 1, p & 1
            t1 = (b_s & modw) if a0 else jnp.zeros_like(b_s)
            t2 = ((bx << 1) & modw) if a1 else jnp.zeros_like(b_s)
            planes.append(_chain_eval(t1, t2, mask_r, w_bits, cpr, jnp, jnp.int32))
        return planes

    _gemv_step(planes_fn, spec.rows, a_ref, b_ref, out_ref)


def _gemv_call(kernel, d, src, src_spec, a_codes, b_codes, k_tile, cost,
               params, interpret):
    m, k = a_codes.shape
    n = b_codes.shape[1]
    assert k % k_tile == 0, (k, k_tile)
    n_pad = _round_up(n, LANES)
    if n_pad != n:  # padded weight columns are computed, then sliced off
        b_codes = jnp.pad(b_codes, ((0, 0), (0, n_pad - n)))
    out = pl.pallas_call(
        kernel,
        grid=(d, k // k_tile),
        in_specs=[
            src_spec,
            pl.BlockSpec((m, k_tile), lambda i, j: (0, j)),
            pl.BlockSpec((k_tile, n_pad), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, m, n_pad), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((d, m, n_pad), jnp.int32),
        cost_estimate=pl.CostEstimate(**cost),
        compiler_params=params,
        interpret=interpret,
    )(src, a_codes, b_codes)
    return out if n_pad == n else out[:, :, :n]


@functools.partial(jax.jit, static_argnames=("k_tile", "interpret"))
def table_gemv_pallas(
    small: jnp.ndarray,           # (R, D, 4, B) int32 per-row config tables
    a_codes: jnp.ndarray,         # (M, K) int32 operand-A codes (config-shared)
    b_codes: jnp.ndarray,         # (K, N) int32 operand-B codes
    k_tile: int | None = None,
    interpret: bool = True,
) -> jnp.ndarray:
    """Batched table-matmul: (D, M, N) int32 from the per-row tables.

    K must divide by ``k_tile`` (fastapp pads the codes with zeros); ``None``
    resolves the registry default for this shape bucket.
    """
    rows, d, four, b_in = small.shape
    m, k = a_codes.shape
    n = b_codes.shape[1]
    assert four == 4 and b_codes.shape[0] == k, (small.shape, b_codes.shape)
    spec = registry.get("fastapp.pallas")
    if k_tile is None:
        bucket = spec.bucket(n_bits=b_in.bit_length() - 1, d=d, m=m, k=k, n=n)
        k_tile = spec.default_tiles(bucket)["k_tile"]
    b_pad = _round_up(b_in, LANES)
    if b_pad != b_in:
        small = jnp.pad(small, ((0, 0), (0, 0), (0, 0), (0, b_pad - b_in)))
    cost = spec.cost_estimate(d=d, m=m, k=k, n=n, a=b_in, rows=rows)
    params = spec.compiler_params(m=m, k_tile=k_tile, n=n, a=b_in, rows=rows)
    return _gemv_call(
        functools.partial(_table_kernel, rows=rows), d, small,
        pl.BlockSpec((rows, 1, 4, b_pad), lambda i, j: (0, i, 0, 0)),
        a_codes, b_codes, k_tile, cost, params, interpret,
    )


@functools.partial(jax.jit, static_argnames=("n_bits", "k_tile", "interpret"))
def entry_gemv_pallas(
    masks: jnp.ndarray,           # (D, R) int32 per-row config masks
    a_codes: jnp.ndarray,         # (M, K) int32 operand-A codes (config-shared)
    b_codes: jnp.ndarray,         # (K, N) int32 operand-B codes
    n_bits: int,
    k_tile: int | None = None,
    interpret: bool = True,
) -> jnp.ndarray:
    """Table-free twin of :func:`table_gemv_pallas`: (D, M, N) int32.

    Bit-identical to the table kernel (the synthesized planes equal the
    gathered tables), with no per-row table build or HBM staging.  Zero-code
    K padding still contributes nothing: every config maps (0, 0) -> 0.
    Signed multipliers only.
    """
    op_spec = spec_for(n_bits)
    d, rows = masks.shape
    m, k = a_codes.shape
    n = b_codes.shape[1]
    assert rows == op_spec.rows, (rows, op_spec.rows)
    assert b_codes.shape[0] == k, (a_codes.shape, b_codes.shape)
    spec = registry.get("fastapp.entry_pallas")
    if k_tile is None:
        bucket = spec.bucket(n_bits=n_bits, d=d, m=m, k=k, n=n)
        k_tile = spec.default_tiles(bucket)["k_tile"]
    cost = spec.cost_estimate(d=d, m=m, k=k, n=n, a=op_spec.n_inputs,
                              rows=rows, width=op_spec.width)
    params = spec.compiler_params(m=m, k_tile=k_tile, n=n, a=op_spec.n_inputs,
                                  rows=rows)
    # configs reach the kernel 8 mask rows at a time (the SMEM block tiling
    # rule); step d reads row d % 8 of block d // 8
    d_rows = 8 if d % 8 == 0 else d
    return _gemv_call(
        functools.partial(_entry_kernel, n_bits=n_bits,
                          b_pad=_round_up(op_spec.n_inputs, LANES)),
        d, masks,
        pl.BlockSpec((d_rows, rows), lambda i, j: (i // d_rows, 0),
                     memory_space=pltpu.SMEM),
        a_codes, b_codes, k_tile, cost, params, interpret,
    )
