"""Accelerator-native application-BEHAV engine (the apps' ``backend="jax"`` path).

The numpy application substrate scores a ``(D, L)`` config batch one product
table at a time: ``AxOApplication.behav`` builds ``(D, 2^N, 2^N)`` tables on
the host and each app loops D python iterations of fancy-indexed gathers.
After the fastchar engine (PR 1) removed operator-level characterization from
the DSE critical path, this loop dominates every ``run_dse`` with an
application objective.  This module evaluates the same app pipelines in a
handful of device dispatches built around three interchangeable table-
arithmetic implementations:

  ``impl="gemm"`` (default off-TPU) -- **pair-plane masked GEMM**.  The
      operator's row structure gives ``T_d[a, b] = sum_r 4^r S_d[r,
      pair_r(a), b]`` with ``pair_r(a)`` one of 4 values, so a table-matmul
      collapses to R dense f32 GEMMs against the *tiny* per-row config tables
      (``fastchar``'s ``(R, D, 4, 2^N)`` gather) -- no per-element table
      lookups and no full product tables at all.  Every intermediate is an
      integer below 2^24, so the f32 GEMMs are bit-exact (asserted in tests).
  ``impl="xla"`` -- flattened ``jnp.take`` gathers + integer reductions over
      device-resident product tables, tiled over cache-sized config chunks
      with ``lax.map`` like ``fastchar.behav_partials``.  Per-config operand
      codes (the FFN's re-quantized activations) always take this path.
  ``impl="pallas"`` (default on TPU for config-shared matmuls) -- the batched
      table-GEMV kernel in ``kernels.app_kernels`` that keeps each config's
      table VMEM-resident across the K reduction (interpret-mode on CPU).
  ``impl="entry"`` / ``impl="entry_pallas"`` -- **table-free** twins.  The
      per-row ``(4, B)`` planes are synthesized on device directly from the
      ``(D, R)`` config masks by the carry-chain model
      (``fastchar._synth_small_jax`` for the XLA path, in-kernel
      ``_chain_eval`` for the Pallas GEMV), so neither the host row-table
      gather nor the ``(D, 2^N, 2^N)`` product-table build ever runs --
      which is what admits 12-bit operands, where the full table would be
      67 MB *per config*.  Bit-identical to the table paths by construction
      (the synthesized planes equal the gathered ones; asserted in tests).

Per-app BEHAV heads combine integer device outputs (logit argmax mismatch
counts, filtered signals, conv outputs) on the host in float64 with exactly
the oracle's expressions, which keeps every app BEHAV metric bit-identical to
the numpy path (count-based *and* float).

Execution policy rides on the :class:`TableBatch` itself: ``table_batch(...,
ctx=ExecutionContext(...))`` gives every primitive scoring that batch the same
kernel-impl preference and config-axis mesh sharding (``shard_map`` over the D
axis; per-config scores are independent, so sharded results are bit-identical
to the unsharded dispatch).

Everything is opt-in: importing this module pulls in JAX; ``repro.apps``
modules import it lazily when a caller passes ``backend="jax"``.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from ..core.engine import MESH_AXIS, ExecutionContext
from ..core.fastchar import _device_tables, _gather_small, _synth_small_jax
from ..obs import telemetry as obs
from ..core.operator_model import OperatorSpec, config_to_masks, spec_for

__all__ = [
    "TableBatch",
    "table_batch",
    "default_matmul_impl",
    "product_tables_jax",
    "table_matmul_jax",
    "table_conv1d_jax",
    "table_conv2d_jax",
    "mismatch_counts",
    "app_behav_jax",
    "multi_app_behav_jax",
]

MATMUL_IMPLS = ("gemm", "xla", "pallas", "entry", "entry_pallas")
# impls that score straight from the config masks, never building tables
_ENTRY_IMPLS = ("entry", "entry_pallas")


def default_matmul_impl() -> str:
    """Pallas table-GEMV on TPU, pair-plane GEMM elsewhere (interpret-mode
    Pallas is a correctness twin, not a CPU fast path)."""
    from ..kernels.ops import on_tpu

    return "pallas" if on_tpu() else "gemm"


# ---------------------------------------------------------------------------
# Device-resident tables
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_bits",))
def _tables_from_small(small: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """(R, D, 4, B) per-row tables -> (D, 2^N, 2^N) int32 product tables."""
    spec = spec_for(n_bits)
    _, _, _, pair_idx = _device_tables(n_bits)
    approx = None
    for r in range(spec.rows):
        term = jnp.take(small[r], pair_idx[r], axis=1) << (2 * r)  # (D, A, B)
        approx = term if approx is None else approx + term
    return approx


@dataclass
class TableBatch:
    """A config batch on device: per-row tables now, full tables on demand.

    ``small`` (the ``(R, D, 4, 2^N)`` per-row config tables, ~4096 ints per
    config) feeds the pair-plane GEMM paths; the full ``(D, 2^N, 2^N)``
    product tables are only reconstructed when a gather/Pallas path asks.
    """

    masks: jnp.ndarray | None        # (D, R) int32, None when built from tables
    n_bits: int
    ctx: ExecutionContext | None = None  # execution policy for the primitives
    _small: jnp.ndarray | None = field(default=None, repr=False)
    _tables: jnp.ndarray | None = field(default=None, repr=False)
    _entry_small: jnp.ndarray | None = field(default=None, repr=False)

    def __len__(self) -> int:
        src = self.masks if self.masks is not None else self._tables
        return src.shape[0]

    @property
    def n_codes(self) -> int:
        return 1 << self.n_bits

    @property
    def small(self) -> jnp.ndarray:
        if self._small is None:
            if self.masks is None:
                raise ValueError(
                    "TableBatch built from raw product tables has no per-row "
                    "tables; construct it with table_batch(spec, configs) to "
                    "use the pair-plane GEMM paths"
                )
            self._small = _gather_small(self.masks, self.n_bits)
        return self._small

    @property
    def has_small(self) -> bool:
        return self._small is not None or self.masks is not None

    @property
    def entry_small(self) -> jnp.ndarray:
        """Per-row planes synthesized on device from the masks (table-free:
        carry-chain evaluation, no host row-table gather).  Bit-identical to
        ``small``; cached separately so the entry paths share one synthesis
        across every app head scoring this batch."""
        if self._entry_small is None:
            if self.masks is None:
                raise ValueError(
                    "TableBatch built from raw product tables has no config "
                    "masks; construct it with table_batch(spec, configs) to "
                    "use the table-free entry paths"
                )
            self._entry_small = _synth_small_jax(self.masks, self.n_bits)
        return self._entry_small

    @property
    def tables(self) -> jnp.ndarray:
        if self._tables is None:
            self._tables = _tables_from_small(self.small, self.n_bits)
        return self._tables


def table_batch(
    spec: OperatorSpec, configs: np.ndarray, ctx: ExecutionContext | None = None
) -> TableBatch:
    """(D, L) {0,1} configs -> device TableBatch for this operator family.

    The batch carries ``ctx`` so every primitive scoring it inherits the same
    execution policy (kernel impl preference, config-axis mesh sharding)
    without each app head having to thread a context through its signature.
    """
    configs = np.atleast_2d(np.asarray(configs)).astype(np.uint8)
    masks = jnp.asarray(config_to_masks(spec, configs).astype(np.int32))
    return TableBatch(masks=masks, n_bits=spec.n_bits, ctx=ctx)


def _as_batch(tables) -> TableBatch:
    if isinstance(tables, TableBatch):
        return tables
    tables = jnp.asarray(tables, jnp.int32)
    if tables.ndim == 2:  # single table, like the numpy behav_from_tables
        tables = tables[None]
    n_bits = int(tables.shape[-1]).bit_length() - 1
    return TableBatch(masks=None, n_bits=n_bits, _tables=tables)


def product_tables_jax(spec: OperatorSpec, configs: np.ndarray) -> jnp.ndarray:
    """(D, L) {0,1} configs -> device (D, 2^N, 2^N) int32 product tables.

    Bit-identical to ``operator_model.product_tables`` (same row tables, same
    carry-truncation semantics; parity is asserted in tests).
    """
    return table_batch(spec, configs).tables


# ---------------------------------------------------------------------------
# Pair-plane GEMM cores (impl="gemm")
# ---------------------------------------------------------------------------
#
# f32 exactness: every GEMM operand/partial is an integer of magnitude at most
# K * max|S_r| = K * 2^(n_bits+1) (guarded < 2^24 by _gemm_ok), and the int32
# combine of the <= R shifted row results stays below 2^31.


def _gemm_ok(k: int, n_bits: int) -> bool:
    return k * (1 << (n_bits + 1)) < (1 << 24)


def _pair_planes(a: jnp.ndarray, k: int, r: int) -> jnp.ndarray:
    """(..., K) codes -> (..., 4K) f32 one-hot over (pair_r(code), k)."""
    pair = 2 * ((a >> (2 * r)) & 1) + ((a >> (2 * r + 1)) & 1)
    q = pair * k + jnp.arange(k, dtype=jnp.int32)
    lead = a.shape[:-1]
    onehot = jnp.zeros(lead + (4 * k,), jnp.float32)
    idx = tuple(
        jnp.arange(s).reshape((1,) * i + (-1,) + (1,) * (len(lead) - i))
        for i, s in enumerate(lead)
    )
    return onehot.at[idx + (q,)].set(1.0)


@functools.partial(jax.jit, static_argnames=("n_bits",))
def _matmul_gemm(small, a, b, n_bits: int):
    """small (R, D, 4, B); a (M, K); b (K, N) -> (D, M, N) int32."""
    spec = spec_for(n_bits)
    d = small.shape[1]
    k = a.shape[1]
    n = b.shape[1]
    out = None
    for r in range(spec.rows):
        a1 = _pair_planes(a, k, r)                              # (M, 4K)
        w = jnp.take(small[r], b, axis=2).reshape(d, 4 * k, n)  # (D, 4K, N)
        res = jnp.einsum("mq,dqn->dmn", a1, w.astype(jnp.float32))
        term = res.astype(jnp.int32) << (2 * r)
        out = term if out is None else out + term
    return out


@functools.partial(jax.jit, static_argnames=("n_bits",))
def _contract_gemm_flat(small, a, bvec, n_bits: int):
    """small (R, D, 4, B); a (M, K) windows; bvec (K,) taps -> (D, M) int32.

    The N=1 table-matmul (every conv is one): a single (D, 4K) x (4K, M) GEMM
    per row instead of the batched einsum.
    """
    spec = spec_for(n_bits)
    d = small.shape[1]
    k = a.shape[1]
    out = None
    for r in range(spec.rows):
        a1 = _pair_planes(a, k, r)                              # (M, 4K)
        w = jnp.take(small[r], bvec, axis=2).reshape(d, 4 * k)  # (D, 4K)
        res = w.astype(jnp.float32) @ a1.T                      # (D, M)
        term = res.astype(jnp.int32) << (2 * r)
        out = term if out is None else out + term
    return out


# ---------------------------------------------------------------------------
# Flattened-gather cores (impl="xla")
# ---------------------------------------------------------------------------


def _pad_leading(x: jnp.ndarray, mult: int) -> jnp.ndarray:
    pad = (-x.shape[0]) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x


@functools.partial(jax.jit, static_argnames=("d_chunk",))
def _matmul_take_shared(tables, a, b, d_chunk: int):
    """tables (D, A, B); a (M, K); b (K, N) -> (D, M, N) int32.

    (M, N, K) gather order keeps the K reduction contiguous in memory.
    """
    d, _, nb = tables.shape
    m, k = a.shape
    n = b.shape[1]
    idx = (a[:, None, :] * nb + b.T[None, :, :]).reshape(-1)   # (M*N*K,)
    tf = tables.reshape(d // d_chunk, d_chunk, -1)

    def chunk(tc):  # (Dc, A*B) -> (Dc, M, N)
        prod = jnp.take(tc, idx, axis=1)
        return prod.reshape(d_chunk, m, n, k).sum(axis=-1)

    return jax.lax.map(chunk, tf).reshape(d, m, n)


@functools.partial(jax.jit, static_argnames=("d_chunk",))
def _matmul_take_batched(tables, a, b, d_chunk: int):
    """tables (D, A, B); a (D, M, K) per-config codes; b (K, N) -> (D, M, N)."""
    d, _, nb = tables.shape
    _, m, k = a.shape
    n = b.shape[1]
    tf = tables.reshape(d // d_chunk, d_chunk, -1)
    af = a.reshape(d // d_chunk, d_chunk, m, k)

    def chunk(args):
        tc, ac = args
        idx = (ac[:, :, :, None] * nb + b[None, None, :, :]).reshape(d_chunk, -1)
        prod = jnp.take_along_axis(tc, idx, axis=1)
        return prod.reshape(d_chunk, m, k, n).sum(axis=2)

    return jax.lax.map(chunk, (tf, af)).reshape(d, m, n)


# ---------------------------------------------------------------------------
# Table-free cores (impl="entry"): per-row gathers from synthesized planes
# ---------------------------------------------------------------------------
#
# Same (M, N, K)-ordered flattened gathers as the impl="xla" cores, but from
# the device-synthesized (R, D, 4, B) planes instead of the (D, A, B) product
# tables: out[d, m, n] = sum_r small[r, d, pair_r(a[m, k]), b[k, n]] << 2r.
# No (D, A, B) intermediate exists at any point, so working-set memory is
# R * 4 * B ints per config at every operand width.


@functools.partial(jax.jit, static_argnames=("n_bits", "d_chunk"))
def _matmul_entry_shared(small, a, b, n_bits: int, d_chunk: int):
    """small (R, D, 4, B); a (M, K); b (K, N) -> (D, M, N) int32."""
    spec = spec_for(n_bits)
    nb = spec.n_inputs
    d = small.shape[1]
    m, k = a.shape
    n = b.shape[1]
    sf = small.transpose(1, 0, 2, 3).reshape(d // d_chunk, d_chunk, spec.rows, -1)
    idxs = [
        (
            ((2 * ((a >> (2 * r)) & 1) + ((a >> (2 * r + 1)) & 1))[:, None, :])
            * nb
            + b.T[None, :, :]
        ).reshape(-1)
        for r in range(spec.rows)
    ]  # per-row (M*N*K,) flat indices into the (4*B,) planes

    def chunk(sc):  # (Dc, R, 4B) -> (Dc, M, N)
        out = None
        for r in range(spec.rows):
            prod = jnp.take(sc[:, r], idxs[r], axis=1)
            term = prod.reshape(d_chunk, m, n, k).sum(axis=-1) << (2 * r)
            out = term if out is None else out + term
        return out

    return jax.lax.map(chunk, sf).reshape(d, m, n)


@functools.partial(jax.jit, static_argnames=("n_bits", "d_chunk"))
def _matmul_entry_batched(small, a, b, n_bits: int, d_chunk: int):
    """small (R, D, 4, B); a (D, M, K) per-config codes; b (K, N) -> (D, M, N)."""
    spec = spec_for(n_bits)
    nb = spec.n_inputs
    d = small.shape[1]
    _, m, k = a.shape
    n = b.shape[1]
    sf = small.transpose(1, 0, 2, 3).reshape(d // d_chunk, d_chunk, spec.rows, -1)
    af = a.reshape(d // d_chunk, d_chunk, m, k)

    def chunk(args):
        sc, ac = args
        out = None
        for r in range(spec.rows):
            pair = 2 * ((ac >> (2 * r)) & 1) + ((ac >> (2 * r + 1)) & 1)
            idx = (pair[:, :, :, None] * nb + b[None, None, :, :]).reshape(
                d_chunk, -1
            )
            prod = jnp.take_along_axis(sc[:, r], idx, axis=1)
            term = prod.reshape(d_chunk, m, k, n).sum(axis=2) << (2 * r)
            out = term if out is None else out + term
        return out

    return jax.lax.map(chunk, (sf, af)).reshape(d, m, n)


def _pad_small(small: jnp.ndarray, mult: int) -> jnp.ndarray:
    """Pad the D axis (axis 1) of (R, D, 4, B) planes with zeros."""
    pad = (-small.shape[1]) % mult
    if pad:
        z = jnp.zeros((small.shape[0], pad) + small.shape[2:], small.dtype)
        small = jnp.concatenate([small, z], axis=1)
    return small


def _windows_1d(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """(T,) -> (T-k+1, k) valid-mode sliding windows."""
    t = x.shape[0]
    return x[jnp.arange(t - k + 1)[:, None] + jnp.arange(k)[None, :]]


def _windows_2d(img: jnp.ndarray, kh: int, kw: int) -> jnp.ndarray:
    """(H, W) -> (H-kh+1, W-kw+1, kh, kw) valid-mode sliding windows."""
    h, w = img.shape
    oy, ox = h - kh + 1, w - kw + 1
    return img[
        jnp.arange(oy)[:, None, None, None] + jnp.arange(kh)[None, None, :, None],
        jnp.arange(ox)[None, :, None, None] + jnp.arange(kw)[None, None, None, :],
    ]


@jax.jit
def _conv1d_take(tables, x, h):
    d, _, nb = tables.shape
    t, k = x.shape[0], h.shape[0]
    win = _windows_1d(x, k)                                 # (T', k)
    idx = (win * nb + h[None, :]).reshape(-1)
    prod = jnp.take(tables.reshape(d, -1), idx, axis=1)
    return prod.reshape(d, t - k + 1, k).sum(axis=2)


@functools.partial(jax.jit, static_argnames=("d_chunk",))
def _conv2d_take(tables, img, kern, d_chunk: int):
    d, _, nb = tables.shape
    kh, kw = kern.shape
    win = _windows_2d(img, kh, kw)                          # (oy, ox, kh, kw)
    oy, ox = win.shape[0], win.shape[1]
    idx = (win * nb + kern[None, None, :, :]).reshape(-1)
    tf = tables.reshape(d // d_chunk, d_chunk, -1)

    def chunk(tc):
        prod = jnp.take(tc, idx, axis=1)
        return prod.reshape(d_chunk, oy, ox, kh * kw).sum(axis=-1)

    return jax.lax.map(chunk, tf).reshape(d, oy, ox)


# ---------------------------------------------------------------------------
# Public primitives
# ---------------------------------------------------------------------------


def _resolve_impl(impl: str | None, batch: TableBatch, k: int) -> str:
    explicit = impl is not None
    if impl is None and batch.ctx is not None:
        # context preference is auto-with-preference, not a hard per-call ask:
        # it may still fall back when the named impl cannot run this batch
        # (the menu itself comes from the kernel registry's fastapp specs)
        impl = batch.ctx.resolve_impl("fastapp")
    impl = default_matmul_impl() if impl is None else impl
    if impl not in MATMUL_IMPLS:
        raise ValueError(f"unknown fastapp impl {impl!r}")
    if impl == "pallas" and not batch.has_small:
        if explicit:
            raise ValueError(
                "impl='pallas' unavailable: TableBatch built from raw tables "
                "has no per-row tables"
            )
        impl = "xla"
    if impl == "gemm" and not (batch.has_small and _gemm_ok(k, batch.n_bits)):
        if explicit:  # never silently hand back a different impl than asked for
            raise ValueError(
                "impl='gemm' unavailable: "
                + (
                    f"K={k} exceeds the f32-exactness bound for {batch.n_bits}-bit"
                    if batch.has_small
                    else "TableBatch built from raw tables has no per-row tables"
                )
            )
        impl = "xla"  # auto-selection falls back to the gather path
    if impl in _ENTRY_IMPLS and batch.masks is None:
        if explicit:
            raise ValueError(
                f"impl={impl!r} unavailable: TableBatch built from raw tables "
                "has no config masks to synthesize entries from"
            )
        impl = "xla"
    return impl


def _config_mesh_ctx(batch: TableBatch, d: int) -> ExecutionContext | None:
    """The batch's context iff it shards 'configs' and ``d`` divides evenly."""
    ctx = batch.ctx
    if ctx is None or not ctx.shards("configs") or d % ctx.device_count:
        return None
    return ctx


# Cached jit(shard_map(primitive)) builders, keyed by (frozen) context plus
# the closure's static parameters -- building a fresh shard_map per call would
# retrace and recompile every dispatch.  Builders whose static parameter is a
# *tunable* tile (the gather paths' d_chunk) key on (context, shape bucket)
# instead and keep the tile in the value, so a re-tuned bucket replaces its
# entry in place rather than leaving a stale compiled executable pinned.

_SHARDED_TAKE_CACHE: dict = {}


def _sharded_by_bucket(key, tiles, build):
    hit = _SHARDED_TAKE_CACHE.get(key)
    if hit is not None and hit[0] == tiles:
        return hit[1]
    ctx = next((k for k in key if isinstance(k, ExecutionContext)), None)
    obs.of(ctx).count("shard.rebuild.fastapp")
    fn = build()
    _SHARDED_TAKE_CACHE[key] = (tiles, fn)
    return fn


@functools.lru_cache(maxsize=None)
def _sharded_matmul_gemm(ctx: ExecutionContext, n_bits: int):
    from jax.sharding import PartitionSpec as P

    return jax.jit(ctx.shard_call(
        lambda s, a, b: _matmul_gemm(s, a, b, n_bits),
        in_specs=(P(None, MESH_AXIS), P(), P()), out_specs=P(MESH_AXIS),
    ))


def _sharded_matmul_take_shared(ctx: ExecutionContext, d_chunk: int, bucket):
    from jax.sharding import PartitionSpec as P

    return _sharded_by_bucket(
        ("take_shared", ctx, bucket), d_chunk,
        lambda: jax.jit(ctx.shard_call(
            lambda t, a, b: _matmul_take_shared(t, a, b, d_chunk),
            in_specs=(P(MESH_AXIS), P(), P()), out_specs=P(MESH_AXIS),
        )),
    )


def _sharded_matmul_take_batched(ctx: ExecutionContext, d_chunk: int, bucket):
    from jax.sharding import PartitionSpec as P

    return _sharded_by_bucket(
        ("take_batched", ctx, bucket), d_chunk,
        lambda: jax.jit(ctx.shard_call(
            lambda t, a, b: _matmul_take_batched(t, a, b, d_chunk),
            in_specs=(P(MESH_AXIS), P(MESH_AXIS), P()), out_specs=P(MESH_AXIS),
        )),
    )


def _sharded_matmul_entry_shared(ctx: ExecutionContext, n_bits: int,
                                 d_chunk: int, bucket):
    from jax.sharding import PartitionSpec as P

    return _sharded_by_bucket(
        ("entry_shared", ctx, bucket), d_chunk,
        lambda: jax.jit(ctx.shard_call(
            lambda s, a, b: _matmul_entry_shared(s, a, b, n_bits, d_chunk),
            in_specs=(P(None, MESH_AXIS), P(), P()), out_specs=P(MESH_AXIS),
        )),
    )


def _sharded_matmul_entry_batched(ctx: ExecutionContext, n_bits: int,
                                  d_chunk: int, bucket):
    from jax.sharding import PartitionSpec as P

    return _sharded_by_bucket(
        ("entry_batched", ctx, bucket), d_chunk,
        lambda: jax.jit(ctx.shard_call(
            lambda s, a, b: _matmul_entry_batched(s, a, b, n_bits, d_chunk),
            in_specs=(P(None, MESH_AXIS), P(MESH_AXIS), P()),
            out_specs=P(MESH_AXIS),
        )),
    )


def _sharded_entry_gemv(ctx: ExecutionContext, n_bits: int, k_tile: int,
                        interpret: bool, bucket):
    from jax.sharding import PartitionSpec as P

    from ..kernels.app_kernels import entry_gemv_pallas

    return _sharded_by_bucket(
        ("entry_gemv", ctx, interpret, bucket), k_tile,
        lambda: jax.jit(ctx.shard_call(
            lambda mk, a, b: entry_gemv_pallas(
                mk, a, b, n_bits, k_tile=k_tile, interpret=interpret
            ),
            in_specs=(P(MESH_AXIS), P(), P()), out_specs=P(MESH_AXIS),
        )),
    )


@functools.lru_cache(maxsize=None)
def _sharded_contract_gemm_flat(ctx: ExecutionContext, n_bits: int):
    from jax.sharding import PartitionSpec as P

    return jax.jit(ctx.shard_call(
        lambda s, w, v: _contract_gemm_flat(s, w, v, n_bits),
        in_specs=(P(None, MESH_AXIS), P(), P()), out_specs=P(MESH_AXIS),
    ))


@functools.lru_cache(maxsize=None)
def _sharded_conv1d_take(ctx: ExecutionContext):
    from jax.sharding import PartitionSpec as P

    return jax.jit(ctx.shard_call(
        _conv1d_take, in_specs=(P(MESH_AXIS), P(), P()), out_specs=P(MESH_AXIS),
    ))


@functools.lru_cache(maxsize=None)
def _sharded_conv2d_take(ctx: ExecutionContext, d_chunk: int):
    from jax.sharding import PartitionSpec as P

    return jax.jit(ctx.shard_call(
        lambda t, im, kk: _conv2d_take(t, im, kk, d_chunk),
        in_specs=(P(MESH_AXIS), P(), P()), out_specs=P(MESH_AXIS),
    ))


def table_matmul_jax(
    tables,
    a_codes,
    b_codes,
    d_chunk: int | None = None,
    impl: str | None = None,
    k_tile: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Batched table matmul: (D, M, N) int32, every multiply a table lookup.

    ``tables`` is a ``TableBatch`` (preferred: enables the pair-plane GEMM
    path) or a raw ``(D, 2^N, 2^N)`` array.  ``a_codes`` is ``(M, K)`` (shared
    across configs) or ``(D, M, K)`` (per-config, e.g. the re-quantized hidden
    activations of the FFN app -- always the XLA gather path).  ``None``
    block shapes (the gather path's ``d_chunk``, the Pallas path's
    ``k_tile``) resolve through the kernel registry under the batch
    context's ``tuning`` policy.
    """
    from ..kernels.tuning import tiles_for

    batch = _as_batch(tables)
    a = jnp.asarray(a_codes, jnp.int32)
    b = jnp.asarray(b_codes, jnp.int32)
    d = len(batch)
    m, k, n = a.shape[-2], a.shape[-1], b.shape[1]
    impl = _resolve_impl(impl, batch, k)
    obs.of(batch.ctx).count(f"dispatch.fastapp.{impl}")
    mesh_ctx = _config_mesh_ctx(batch, d)

    if a.ndim == 2 and impl == "gemm":
        if mesh_ctx is not None:
            return _sharded_matmul_gemm(mesh_ctx, batch.n_bits)(batch.small, a, b)
        return _matmul_gemm(batch.small, a, b, batch.n_bits)

    if a.ndim == 2 and impl == "pallas":
        from ..kernels.app_kernels import table_gemv_pallas
        from ..kernels.ops import on_tpu

        interpret = (not on_tpu()) if interpret is None else interpret
        if k_tile is None:
            k_tile = tiles_for(batch.ctx, "fastapp.pallas",
                               n_bits=batch.n_bits, d=d, m=m, k=k, n=n)["k_tile"]
        k_tile = min(k_tile, max(k, 1))
        pad = (-k) % k_tile
        if pad:  # zero codes index table[0, 0] == 0: padding adds nothing
            a = jnp.concatenate([a, jnp.zeros((a.shape[0], pad), jnp.int32)], axis=1)
            b = jnp.concatenate([b, jnp.zeros((pad, b.shape[1]), jnp.int32)], axis=0)
        return table_gemv_pallas(batch.small, a, b, k_tile=k_tile,
                                 interpret=interpret)

    if a.ndim == 2 and impl == "entry_pallas":
        from ..kernels.app_kernels import entry_gemv_pallas
        from ..kernels.ops import on_tpu

        interpret = (not on_tpu()) if interpret is None else interpret
        if k_tile is None:
            k_tile = tiles_for(batch.ctx, "fastapp.entry_pallas",
                               n_bits=batch.n_bits, d=d, m=m, k=k, n=n)["k_tile"]
        k_tile = min(k_tile, max(k, 1))
        pad = (-k) % k_tile
        if pad:  # zero codes map through entry (0, 0) -> 0: padding is inert
            a = jnp.concatenate([a, jnp.zeros((a.shape[0], pad), jnp.int32)], axis=1)
            b = jnp.concatenate([b, jnp.zeros((pad, b.shape[1]), jnp.int32)], axis=0)
        if mesh_ctx is not None:
            from ..kernels import registry

            bucket = registry.get("fastapp.entry_pallas").bucket(
                n_bits=batch.n_bits, d=d, m=m, k=k, n=n
            )
            return _sharded_entry_gemv(
                mesh_ctx, batch.n_bits, k_tile, interpret, bucket
            )(batch.masks, a, b)
        return entry_gemv_pallas(
            batch.masks, a, b, batch.n_bits, k_tile=k_tile, interpret=interpret
        )

    if impl in _ENTRY_IMPLS:
        # table-free gather path ("entry", or "entry_pallas" with per-config
        # operand codes, which the GEMV kernel does not cover): chunked
        # per-row gathers from the device-synthesized planes
        if d_chunk is None:
            d_chunk = tiles_for(batch.ctx, "fastapp.entry",
                                n_bits=batch.n_bits, d=d, m=m, k=k, n=n)["d_chunk"]
        if mesh_ctx is not None:
            from ..kernels import registry

            # per-shard chunking, same story as the xla gather path: shrink
            # d_chunk so it divides the local config slice exactly (no pad
            # inside the shard), key the cache on the full shape bucket
            dc = math.gcd(d // mesh_ctx.device_count, d_chunk)
            bucket = registry.get("fastapp.entry").bucket(
                n_bits=batch.n_bits, d=d, m=m, k=k, n=n
            ) + (a.ndim,)
            if a.ndim == 3:
                return _sharded_matmul_entry_batched(
                    mesh_ctx, batch.n_bits, dc, bucket
                )(batch.entry_small, a, b)
            return _sharded_matmul_entry_shared(
                mesh_ctx, batch.n_bits, dc, bucket
            )(batch.entry_small, a, b)
        d_chunk = min(d_chunk, d)
        sp = _pad_small(batch.entry_small, d_chunk)
        if a.ndim == 3:
            out = _matmul_entry_batched(
                sp, _pad_leading(a, d_chunk), b, batch.n_bits, d_chunk
            )
        else:
            out = _matmul_entry_shared(sp, a, b, batch.n_bits, d_chunk)
        return out[:d]

    if d_chunk is None:
        d_chunk = tiles_for(batch.ctx, "fastapp.xla",
                            n_bits=batch.n_bits, d=d, m=m, k=k, n=n)["d_chunk"]
    if mesh_ctx is not None and impl == "xla":
        from ..kernels import registry

        # per-shard chunking: shrink d_chunk so it divides the local slice
        dc = math.gcd(d // mesh_ctx.device_count, d_chunk)
        # the full registry shape bucket (n_bits, d, m, k, n) + operand rank:
        # distinct app heads (different m/k/n -> different tuned d_chunk) get
        # distinct entries instead of thrashing one (n_bits, d) slot
        bucket = registry.get("fastapp.xla").bucket(
            n_bits=batch.n_bits, d=d, m=m, k=k, n=n
        ) + (a.ndim,)
        if a.ndim == 3:
            return _sharded_matmul_take_batched(mesh_ctx, dc, bucket)(
                batch.tables, a, b
            )
        return _sharded_matmul_take_shared(mesh_ctx, dc, bucket)(
            batch.tables, a, b
        )

    d_chunk = min(d_chunk, d)
    tp = _pad_leading(batch.tables, d_chunk)
    if a.ndim == 3:
        out = _matmul_take_batched(tp, _pad_leading(a, d_chunk), b, d_chunk)
    else:
        out = _matmul_take_shared(tp, a, b, d_chunk)
    return out[:d]


def table_conv1d_jax(tables, x_codes, h_codes, impl: str | None = None) -> jnp.ndarray:
    """Valid-mode 1-D correlation through per-config tables: (D, T-k+1) int32."""
    batch = _as_batch(tables)
    x = jnp.asarray(x_codes, jnp.int32)
    h = jnp.asarray(h_codes, jnp.int32)
    impl = _resolve_impl(impl, batch, h.shape[0])
    mesh_ctx = _config_mesh_ctx(batch, len(batch))
    if impl in _ENTRY_IMPLS and _gemm_ok(h.shape[0], batch.n_bits):
        # table-free: same flat contract as "gemm", fed by synthesized planes
        # (the sharded builder is shape-generic in the (R, D, 4, B) planes,
        # so the entry path rides the identical shard_map)
        win = _windows_1d(x, h.shape[0])
        if mesh_ctx is not None:
            return _sharded_contract_gemm_flat(mesh_ctx, batch.n_bits)(
                batch.entry_small, win, h
            )
        return _contract_gemm_flat(batch.entry_small, win, h, batch.n_bits)
    if impl == "gemm":
        win = _windows_1d(x, h.shape[0])
        if mesh_ctx is not None:
            return _sharded_contract_gemm_flat(mesh_ctx, batch.n_bits)(
                batch.small, win, h
            )
        return _contract_gemm_flat(batch.small, win, h, batch.n_bits)
    if mesh_ctx is not None and impl == "xla":
        return _sharded_conv1d_take(mesh_ctx)(batch.tables, x, h)
    return _conv1d_take(batch.tables, x, h)


def table_conv2d_jax(
    tables, img_codes, k_codes, d_chunk: int = 16, impl: str | None = None
) -> jnp.ndarray:
    """Valid-mode 2-D convolution through per-config tables: (D, H', W') int32."""
    batch = _as_batch(tables)
    img = jnp.asarray(img_codes, jnp.int32)
    kern = jnp.asarray(k_codes, jnp.int32)
    impl = _resolve_impl(impl, batch, int(kern.size))
    d = len(batch)
    mesh_ctx = _config_mesh_ctx(batch, d)
    if impl in _ENTRY_IMPLS and _gemm_ok(int(kern.size), batch.n_bits):
        kh, kw = kern.shape
        win = _windows_2d(img, kh, kw)
        oy, ox = win.shape[0], win.shape[1]
        if mesh_ctx is not None:
            out = _sharded_contract_gemm_flat(mesh_ctx, batch.n_bits)(
                batch.entry_small, win.reshape(oy * ox, kh * kw),
                kern.reshape(-1),
            )
        else:
            out = _contract_gemm_flat(
                batch.entry_small, win.reshape(oy * ox, kh * kw),
                kern.reshape(-1), batch.n_bits,
            )
        return out.reshape(d, oy, ox)
    if impl == "gemm":
        kh, kw = kern.shape
        win = _windows_2d(img, kh, kw)
        oy, ox = win.shape[0], win.shape[1]
        if mesh_ctx is not None:
            out = _sharded_contract_gemm_flat(mesh_ctx, batch.n_bits)(
                batch.small, win.reshape(oy * ox, kh * kw), kern.reshape(-1)
            )
        else:
            out = _contract_gemm_flat(
                batch.small, win.reshape(oy * ox, kh * kw), kern.reshape(-1),
                batch.n_bits,
            )
        return out.reshape(d, oy, ox)
    if mesh_ctx is not None and impl == "xla":
        dc = math.gcd(d // mesh_ctx.device_count, d_chunk)
        return _sharded_conv2d_take(mesh_ctx, dc)(batch.tables, img, kern)
    d_chunk = min(d_chunk, d)
    out = _conv2d_take(_pad_leading(batch.tables, d_chunk), img, kern, d_chunk)
    return out[:d]


# ---------------------------------------------------------------------------
# Jitted BEHAV heads
# ---------------------------------------------------------------------------


@jax.jit
def _argmax_mismatch(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """(D, S, C) integer logits -> (D,) int32 misclassification counts."""
    return jnp.sum(jnp.argmax(logits, axis=-1) != labels[None, :], axis=-1)


def mismatch_counts(
    tables, x_codes, w_codes, labels, d_chunk: int | None = None,
    impl: str | None = None, interpret: bool | None = None,
) -> jnp.ndarray:
    """Classification head: table-GEMV logits -> per-config mismatch counts.

    Integer argmax over integer logits breaks ties exactly like the numpy
    oracle (first maximum), so the resulting error *counts* are bit-identical.
    """
    logits = table_matmul_jax(
        tables, x_codes, w_codes, d_chunk=d_chunk, impl=impl, interpret=interpret
    )
    return _argmax_mismatch(logits, jnp.asarray(np.asarray(labels), jnp.int32))


# ---------------------------------------------------------------------------
# Batch driver
# ---------------------------------------------------------------------------


def multi_app_behav_jax(
    apps, spec: OperatorSpec, configs: np.ndarray, batch: int = 128,
    ctx: ExecutionContext | None = None,
) -> dict[str, np.ndarray]:
    """(D, L) configs -> {app.name: (D,) BEHAV} with ONE shared TableBatch.

    Scoring several applications one at a time re-runs the table gathers per
    app; here each config chunk is staged as a single device ``TableBatch``
    whose lazily-cached ``small``/``tables`` fields are shared by every app's
    ``behav_jax_from_tables`` head -- the multi-app DSE batching used by
    ``benchmarks/bench_apps.py`` (one engine pass for all four heads).
    """
    apps = list(apps)
    configs = np.atleast_2d(np.asarray(configs)).astype(np.uint8)
    d = len(configs)
    out = {app.name: np.empty(d, dtype=np.float64) for app in apps}
    for lo in range(0, d, batch):
        hi = min(lo + batch, d)
        cfgs = configs[lo:hi]
        bucket = min(batch, 1 << max(len(cfgs) - 1, 1).bit_length())
        if ctx is not None and ctx.shards("configs"):
            # a shard-divisible bucket keeps every chunk on the mesh path
            bucket = max(bucket, ctx.device_count)
            bucket += (-bucket) % ctx.device_count
        pad = bucket - len(cfgs)
        if pad:
            cfgs = np.concatenate([cfgs, np.zeros((pad, cfgs.shape[1]), np.uint8)])
        tb = table_batch(spec, cfgs, ctx=ctx)
        for app in apps:
            out[app.name][lo:hi] = app.behav_jax_from_tables(tb)[: hi - lo]
    return out


def app_behav_jax(
    app, spec: OperatorSpec, configs: np.ndarray, batch: int = 128,
    ctx: ExecutionContext | None = None,
) -> np.ndarray:
    """(D, L) configs -> (D,) app BEHAV through the device engine.

    ``batch`` configs at a time are staged as a device ``TableBatch`` and
    handed to the app's ``behav_jax_from_tables`` head; chunking bounds the
    device working set (a (128, 256, 256) int32 table batch is ~33 MB at N=8)
    exactly like the numpy ``AxOApplication.behav`` batching.  Chunks are
    padded up to power-of-two buckets (capped at ``batch``) so the jitted
    kernels compile at most ~log2(batch) distinct D shapes across a whole DSE
    run, however ragged the validated fronts get.
    """
    return multi_app_behav_jax([app], spec, configs, batch=batch, ctx=ctx)[
        app.name
    ]
