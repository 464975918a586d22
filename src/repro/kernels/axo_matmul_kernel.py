"""AxO matmul Pallas kernel -- the paper's operator, TPU-adapted.

An FPGA realizes the approximate multiplier in LUT fabric; a TPU's MXU only
does exact MACs.  The TPU-native decomposition (DESIGN.md §3.2) is

    T[a, b] = a*b + E[a, b]          (E = exact 2^n x 2^n error table)
    E[a, b] ~ sum_r f_r[a] * g_r[b]  (rank-R SVD of E)

so   AxO-matmul(A, B) = A.B  +  sum_r F_r(A) @ G_r(B)

where F_r(A)[m,k] = f_r[A[m,k]] is a per-element 2^n-entry table lookup.  The
correction is R extra MXU matmuls over feature maps -- systolic-friendly, no
gathers in the inner loop (the lookups hit a VMEM-resident (2^n, R) table).

Kernel: classic (M, N, K) blocked matmul; the K grid axis is innermost so the
fp32 accumulator lives in a VMEM scratch across K steps.  Block shapes come
from the kernel registry (spec ``"axo_matmul.pallas"``; ``None`` resolves the
bucket defaults, tuned contexts hand winners down through ``axo_linear`` /
``AxODeployment``), as do the ``pl.CostEstimate`` and compiler params.
Arbitrary (M, K, N) are handled by zero-padding every operand to the block
grid and slicing the output -- exact, because padded *values* and *factors*
are all zero, so padded rows/columns contribute nothing to any dot product
(decode-shaped M=4 activations included; M pads to the f32 sublane multiple
of 8, K/N to lane multiples of 128).

The bit-exact table path (a gather per (m, k, n)) exists only in ref.py as the
oracle; rank sweep accuracy is characterized by repro.axo.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

__all__ = ["axo_matmul_pallas"]


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _kernel(a_ref, b_ref, fa_ref, gb_ref, o_ref, acc_ref, *, n_k: int, rank: int):
    """One (bm, bn) output tile; accumulates over the K grid axis.

    a_ref:  (bm, bk) f32   signed values of A's codes
    b_ref:  (bk, bn) f32   signed values of B's codes
    fa_ref: (R, bm, bk) f32  left error factors F_r(A), precomputed lookups
    gb_ref: (R, bk, bn) f32  right error factors G_r(B)
    """
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    acc = jnp.dot(a, b, preferred_element_type=jnp.float32)
    for r in range(rank):                       # static unroll: R extra matmuls
        # the factors are arbitrary f32 (the values above are small integers,
        # exact in any MXU pass), so the rank terms need full f32 precision
        acc = acc + jnp.dot(
            fa_ref[r], gb_ref[r], preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    acc_ref[...] += acc

    @pl.when(k_step == n_k - 1)
    def _emit():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "interpret"),
)
def axo_matmul_pallas(
    a_vals: jnp.ndarray,         # (M, K) f32 signed operand values
    b_vals: jnp.ndarray,         # (K, N) f32
    fa: jnp.ndarray,             # (R, M, K) f32 left error factors
    gb: jnp.ndarray,             # (R, K, N) f32 right error factors
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool = True,
) -> jnp.ndarray:
    """Blocked AxO matmul; see module docstring.  Returns (M, N) f32."""
    m, k = a_vals.shape
    n = b_vals.shape[1]
    rank = fa.shape[0]
    spec = registry.get("axo_matmul.pallas")
    if bm is None or bn is None or bk is None:
        d = spec.default_tiles(spec.bucket(m=m, k=k, n=n, rank=rank))
        bm = d["bm"] if bm is None else bm
        bn = d["bn"] if bn is None else bn
        bk = d["bk"] if bk is None else bk
    # shrink blocks to the padded problem, never below the f32 min tile (8, 128)
    bm = max(8, min(bm, _round_up(m, 8)))
    bn = max(128, min(bn, _round_up(n, 128)))
    bk = max(128, min(bk, _round_up(k, 128)))
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    # static-shape property, so recording at trace time covers every dispatch
    # of this shape; the fraction of the padded (M, N, K) iteration space
    # spent multiplying zeros
    from ..obs.telemetry import record_pad_waste

    record_pad_waste("axo_matmul", (m, n, k), (mp, np_, kp))
    if (mp, np_, kp) != (m, n, k):
        # exact: padded values and factors are zero, contributing 0 products
        a_vals = jnp.pad(a_vals, ((0, mp - m), (0, kp - k)))
        b_vals = jnp.pad(b_vals, ((0, kp - k), (0, np_ - n)))
        fa = jnp.pad(fa, ((0, 0), (0, mp - m), (0, kp - k)))
        gb = jnp.pad(gb, ((0, 0), (0, kp - k), (0, np_ - n)))
    n_k = kp // bk

    cost = spec.cost_estimate(m=mp, k=kp, n=np_, rank=rank)
    params = spec.compiler_params(bm=bm, bn=bn, bk=bk, rank=rank)
    grid = (mp // bm, np_ // bn, n_k)
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, rank=rank),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((rank, bm, bk), lambda i, j, kk: (0, i, kk)),
            pl.BlockSpec((rank, bk, bn), lambda i, j, kk: (0, kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        cost_estimate=pl.CostEstimate(**cost),
        compiler_params=params,
        interpret=interpret,
    )(a_vals, b_vals, fa, gb)
    return out if (mp, np_) == (m, n) else out[:m, :n]
