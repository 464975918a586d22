"""Mamba-2 SSD (state-space duality) block, chunked (arXiv:2405.21060).

Training/prefill uses the chunked SSD algorithm: quadratic attention-like math
inside fixed-size chunks + a linear recurrence across chunk states, all in plain
einsums/scans so XLA maps it onto the MXU.  Decode is the O(1) recurrent update
carrying (conv window, SSD state).  The Pallas ``ssd_scan`` kernel implements the
same math for the TPU deployment path and is validated against this reference.

With ``axo`` (an ``AxODeployment`` and the mixer's entries) the two
projections, ``in_proj`` and ``out_proj``, run on the approximate operator;
the conv, dt, A, the recurrence, D and the gated norm stay exact.  The gated
norm reads no residual stream, so it takes ``SSMConfig.norm_eps`` where one
is given, not the model's (possibly folded) ``norm_eps``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import rmsnorm
from .sharding import ShardingRules, constrain
from .spec import ParamSpec

__all__ = [
    "mamba_spec",
    "mamba_apply",
    "mamba_decode",
    "mamba_dims",
    "ssd_chunked",
    "ssd_step",
]


def mamba_dims(cfg: ModelConfig) -> dict:
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.d_state
    return {
        "d_inner": d_inner,
        "n_heads": n_heads,
        "conv_dim": conv_dim,
        "d_in_proj": 2 * d_inner + 2 * s.n_groups * s.d_state + n_heads,
    }


def _gated_norm_eps(cfg: ModelConfig) -> float:
    eps = cfg.ssm.norm_eps
    return cfg.norm_eps if eps is None else eps


def _proj(p: dict, name: str, x: jnp.ndarray, axo) -> jnp.ndarray:
    """x @ p[name], on the approximate operator where ``axo`` has an entry."""
    if axo is not None and name in axo[1]:
        return axo[0].apply(x, axo[1][name])
    return x @ p[name]


def mamba_spec(cfg: ModelConfig) -> dict:
    s = cfg.ssm
    dims = mamba_dims(cfg)
    return {
        "in_proj": ParamSpec((cfg.d_model, dims["d_in_proj"]), ("embed", "ssm_inner")),
        "conv_w": ParamSpec((s.d_conv, dims["conv_dim"]), (None, "ssm_inner"), scale=1.0),
        "conv_b": ParamSpec((dims["conv_dim"],), ("ssm_inner",), init="zeros"),
        "a_log": ParamSpec((dims["n_heads"],), (None,), init="ones"),
        "dt_bias": ParamSpec((dims["n_heads"],), (None,), init="zeros"),
        "d_skip": ParamSpec((dims["n_heads"],), (None,), init="ones"),
        "norm": ParamSpec((dims["d_inner"],), ("ssm_inner",), init="ones"),
        "out_proj": ParamSpec((dims["d_inner"], cfg.d_model), ("ssm_inner", "embed")),
    }


def _causal_conv(xbc: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv, window d_conv.  xbc: (B, S, C); w: (K, C)."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(xbc, dtype=jnp.float32)
    for i in range(k):  # tiny static K (4): unrolled adds, no gather
        out = out + pad[:, i : i + xbc.shape[1], :].astype(jnp.float32) * w[i]
    return (out + b).astype(xbc.dtype)


def _segsum(x: jnp.ndarray) -> jnp.ndarray:
    """x (..., Q) -> (..., Q, Q) with out[i, j] = sum_{j < k <= i} x[k]; -inf above diag."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jnp.ndarray,      # (B, S, H, P) inputs
    dt: jnp.ndarray,     # (B, S, H) positive step sizes
    a: jnp.ndarray,      # (H,) negative decay rates
    bmat: jnp.ndarray,   # (B, S, G, N)
    cmat: jnp.ndarray,   # (B, S, G, N)
    chunk: int,
    init_state: jnp.ndarray | None = None,   # (B, H, P, N)
    unroll: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    b, s, h, p = x.shape
    g, n = bmat.shape[2], bmat.shape[3]
    rep = h // g
    q = min(chunk, s)
    pad = (-s) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    nc = sp // q

    xc = x.reshape(b, nc, q, h, p)
    dtc = dt.reshape(b, nc, q, h).astype(jnp.float32)
    bc = bmat.reshape(b, nc, q, g, n)
    cc = cmat.reshape(b, nc, q, g, n)
    # broadcast groups -> heads
    bh = jnp.repeat(bc, rep, axis=3)        # (B,nc,Q,H,N)
    ch = jnp.repeat(cc, rep, axis=3)

    da = dtc * a.astype(jnp.float32)         # (B,nc,Q,H)
    da_cs = jnp.cumsum(da, axis=2)           # inclusive cumsum over chunk

    # 1) intra-chunk (quadratic within chunk)
    l = jnp.exp(_segsum(da.transpose(0, 1, 3, 2)))           # (B,nc,H,Q,Q)
    scores = jnp.einsum("bclhn,bcshn->bchls", ch, bh)         # (B,nc,H,Q,S)
    y_diag = jnp.einsum(
        "bchls,bchls,bcsh,bcshp->bclhp",
        scores, l, dtc, xc.astype(jnp.float32),
    )

    # 2) per-chunk states
    decay_states = jnp.exp(da_cs[:, :, -1:, :] - da_cs)       # (B,nc,Q,H)
    states = jnp.einsum(
        "bcshn,bcsh,bcshp->bchpn", bh, decay_states * dtc, xc.astype(jnp.float32)
    )

    # 3) inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(da_cs[:, :, -1, :])                 # (B,nc,H)
    s0 = (
        jnp.zeros((b, h, p, n), jnp.float32)
        if init_state is None
        else init_state.astype(jnp.float32)
    )

    def body(carry, inp):
        st_in = carry
        st_chunk, dec = inp                                   # (B,H,P,N), (B,H)
        st_out = st_in * dec[:, :, None, None] + st_chunk
        return st_out, st_in                                  # emit state *entering* chunk

    if unroll:
        st = s0
        prev_list = []
        for c in range(nc):
            st, prev = body(st, (states[:, c], chunk_decay[:, c]))
            prev_list.append(prev)
        final = st
        prev_states = jnp.stack(prev_list, axis=1)            # (B,nc,H,P,N)
    else:
        final, prev_states = jax.lax.scan(
            body, s0, (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2))
        )
        prev_states = prev_states.transpose(1, 0, 2, 3, 4)    # (B,nc,H,P,N)

    # 4) inter-chunk output
    y_off = jnp.einsum(
        "bclhn,bchpn,bclh->bclhp", ch, prev_states, jnp.exp(da_cs)
    )

    y = (y_diag + y_off).reshape(b, sp, h, p)[:, :s]
    return y.astype(x.dtype), final


def mamba_apply(
    p: dict,
    xin: jnp.ndarray,                # (B, S, d_model)
    cfg: ModelConfig,
    rules: ShardingRules,
    init_state: jnp.ndarray | None = None,
    axo=None,                        # (AxODeployment, mixer entries)
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
    """Full-sequence forward.  Returns (out, (conv_tail, ssd_state)) for cache."""
    s = cfg.ssm
    dims = mamba_dims(cfg)
    di, h, cd = dims["d_inner"], dims["n_heads"], dims["conv_dim"]

    zxbcdt = _proj(p, "in_proj", xin, axo)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di : di + cd]
    dt_raw = zxbcdt[..., di + cd :]                            # (B,S,H)

    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs = xbc[..., :di].reshape(*xbc.shape[:2], h, s.head_dim)
    bmat = xbc[..., di : di + s.n_groups * s.d_state].reshape(
        *xbc.shape[:2], s.n_groups, s.d_state
    )
    cmat = xbc[..., di + s.n_groups * s.d_state :].reshape(
        *xbc.shape[:2], s.n_groups, s.d_state
    )
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))

    # NOTE: the SSD inter-chunk scan body is elementwise-only (the heavy
    # einsums are outside the scan), so XLA's count-while-bodies-once cost
    # undercount is negligible here -- probes keep the scan (unroll=False).
    y, state = ssd_chunked(
        xs, dt, a, bmat, cmat, chunk=s.chunk, init_state=init_state, unroll=False
    )
    y = y + xs * p["d_skip"].astype(xs.dtype)[None, None, :, None]
    y = y.reshape(*y.shape[:2], di)
    y = rmsnorm(y * jax.nn.silu(z), p["norm"], _gated_norm_eps(cfg))
    out = _proj(p, "out_proj", y, axo)
    # cache: last (d_conv - 1) *pre-activation* conv inputs
    zxbc_raw = zxbcdt[..., di : di + cd]
    conv_tail = zxbc_raw[:, -(s.d_conv - 1) :, :]
    return constrain(out, rules, "batch", "seq", "embed"), (conv_tail, state)


def ssd_step(
    state: jnp.ndarray,  # (B, H, P, N) float32
    x: jnp.ndarray,      # (B, H, P)
    dt: jnp.ndarray,     # (B, H) positive step sizes
    a: jnp.ndarray,      # (H,) negative decay rates
    bmat: jnp.ndarray,   # (B, H, N)
    cmat: jnp.ndarray,   # (B, H, N)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One position of the recurrence: returns (y (B,H,P) float32, new state)."""
    decay = jnp.exp(dt * a)                                    # (B,H)
    new_state = state * decay[:, :, None, None] + jnp.einsum(
        "bhn,bh,bhp->bhpn", bmat, dt, x.astype(jnp.float32)
    )
    return jnp.einsum("bhn,bhpn->bhp", cmat, new_state), new_state


def mamba_decode(
    p: dict,
    xin: jnp.ndarray,                # (B, 1, d_model)
    cfg: ModelConfig,
    rules: ShardingRules,
    conv_state: jnp.ndarray,         # (B, d_conv-1, conv_dim)
    ssd_state: jnp.ndarray,          # (B, H, P, N)
    axo=None,                        # (AxODeployment, mixer entries)
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
    """O(1) single-token step."""
    s = cfg.ssm
    dims = mamba_dims(cfg)
    di, h, cd = dims["d_inner"], dims["n_heads"], dims["conv_dim"]

    zxbcdt = _proj(p, "in_proj", xin[:, 0], axo)               # (B, d_in_proj)
    z = zxbcdt[..., :di]
    xbc_new = zxbcdt[..., di : di + cd]
    dt_raw = zxbcdt[..., di + cd :]

    window = jnp.concatenate([conv_state, xbc_new[:, None]], axis=1)  # (B, d_conv, cd)
    conv = (window.astype(jnp.float32) * p["conv_w"][None].astype(jnp.float32)).sum(1)
    xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32)).astype(xin.dtype)

    xs = xbc[..., :di].reshape(-1, h, s.head_dim)
    bmat = xbc[..., di : di + s.n_groups * s.d_state].reshape(-1, s.n_groups, s.d_state)
    cmat = xbc[..., di + s.n_groups * s.d_state :].reshape(-1, s.n_groups, s.d_state)
    rep = h // s.n_groups
    bh = jnp.repeat(bmat, rep, axis=1)                         # (B,H,N)
    ch = jnp.repeat(cmat, rep, axis=1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    y, new_state = ssd_step(ssd_state, xs, dt, a, bh, ch)
    y = y + xs.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)[None, :, None]
    y = y.reshape(-1, di).astype(xin.dtype)
    y = rmsnorm(y * jax.nn.silu(z), p["norm"], _gated_norm_eps(cfg))
    out = _proj(p, "out_proj", y, axo)[:, None]                 # (B,1,d)
    return constrain(out, rules, "batch", "seq", "embed"), (window[:, 1:], new_state)
