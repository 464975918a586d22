"""Flash attention Pallas kernel (causal + GQA), TPU BlockSpec tiling.

Grid (B, H, nQ, nK) with the KV axis innermost: online-softmax statistics
(m, l) and the fp32 output accumulator live in VMEM scratch across the KV
steps of one (batch, head, q-block).  Causal blocks entirely above the
diagonal are masked cheaply (their contribution underflows to zero through
exp(-inf)); GQA maps each query head to its KV group via index_map, so KV
blocks are fetched once per group -- never materialized per-head.

Block shapes come from the kernel registry (spec ``"flash_attention.pallas"``,
replacing the historical hard-coded ``bq=bk=128``); ``None`` resolves the
bucket defaults, and the registry also supplies the ``pl.CostEstimate`` and
compiler params.  Arbitrary sequence lengths (e.g. seq 192 with bq=128) are
zero-padded to the block grid: padded *query* rows are computed and sliced
off, padded *KV* positions are masked to -inf via the static true KV length
(a zero-padded key would otherwise contribute exp(0) mass to the softmax).

Oracle: kernels.ref.ref_flash_attention; parity swept over shapes/dtypes in
tests/test_kernels.py (interpret=True executes this exact body on CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale: float, causal: bool, kv_len: int, n_k: int,
            bq: int, bk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                        # (bq, hd)
    k = k_ref[0, 0]                        # (bk, hd)
    v = v_ref[0, 0]                        # (bk, hd)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal or kv_len % bk:
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = k_pos < kv_len             # mask zero-padded KV positions
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            valid &= q_pos >= k_pos
        s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _emit():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret", "scale")
)
def flash_attention_pallas(
    q: jnp.ndarray,              # (B, H, Sq, hd)
    k: jnp.ndarray,              # (B, G, Skv, hd)
    v: jnp.ndarray,              # (B, G, Skv, hd)
    causal: bool = True,
    scale: float | None = None,
    bq: int | None = None,
    bk: int | None = None,
    interpret: bool = True,
) -> jnp.ndarray:
    b, h, sq, hd = q.shape
    g, skv = k.shape[1], k.shape[2]
    rep = h // g
    scale = float(1.0 / (hd ** 0.5)) if scale is None else scale
    spec = registry.get("flash_attention.pallas")
    if bq is None or bk is None:
        d = spec.default_tiles(spec.bucket(sq=sq, skv=skv, hd=hd))
        bq = d["bq"] if bq is None else bq
        bk = d["bk"] if bk is None else bk
    # shrink blocks to the padded problem, never below the f32 min sublane/lane
    bq = max(8, min(bq, _round_up(sq, 8)))
    bk = max(128, min(bk, _round_up(skv, 128)))
    sqp, skvp = _round_up(sq, bq), _round_up(skv, bk)
    # static-shape property, so recording at trace time covers every dispatch
    # of this shape; the fraction of the padded (Sq, Skv) score space that is
    # padding (masked to -inf in-kernel)
    from ..obs.telemetry import record_pad_waste

    record_pad_waste("flash_attention", (sq, skv), (sqp, skvp))
    if sqp != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    if skvp != skv:
        # padded KV positions are masked to -inf in-kernel via kv_len
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skvp - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skvp - skv), (0, 0)))
    n_k = skvp // bk

    cost = spec.cost_estimate(b=b, h=h, sq=sqp, skv=skvp, hd=hd, causal=causal)
    params = spec.compiler_params(bq=bq, bk=bk, hd=hd)
    grid = (b, h, sqp // bq, n_k)
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, kv_len=skv, n_k=n_k,
            bq=bq, bk=bk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            # GQA: query head hi reads KV group hi // rep
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bi, hi, qi, ki, rep=rep: (bi, hi // rep, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bi, hi, qi, ki, rep=rep: (bi, hi // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sqp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(**cost),
        compiler_params=params,
        interpret=interpret,
    )(q, k, v)
    return out if sqp == sq else out[:, :, :sq]
