"""Shared neural layers: norms, RoPE, dense/gated MLPs, embeddings."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .spec import ParamSpec

__all__ = [
    "rmsnorm",
    "rope_freqs",
    "apply_rope",
    "sinusoid_pos",
    "mlp_spec",
    "mlp_apply",
    "embed_spec",
]


def sinusoid_pos(positions: jnp.ndarray, d_model: int, base: float = 10_000.0) -> jnp.ndarray:
    """Transformer sinusoidal absolute position embeddings: (S,) -> (S, d)."""
    half = d_model // 2
    freq = jnp.exp(-jnp.log(base) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = positions[:, None].astype(jnp.float32) * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def rmsnorm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * gamma


def rope_freqs(head_dim: int, max_seq: int, theta: float) -> jnp.ndarray:
    """(max_seq, head_dim//2) complex-free cos/sin stacked -> (max_seq, head_dim)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    ang = jnp.outer(t, inv)                      # (S, hd/2)
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)  # (S, hd)


def apply_rope(x: jnp.ndarray, freqs: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S) int32."""
    hd = x.shape[-1]
    f = freqs[positions]                         # (..., S, hd)
    cos, sin = f[..., : hd // 2], f[..., hd // 2 :]
    cos = cos[..., None, :]                      # add head axis
    sin = sin[..., None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_spec(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.act == "swiglu":
        return {
            "w_gate": ParamSpec((d, f), ("embed", "mlp")),
            "w_up": ParamSpec((d, f), ("embed", "mlp")),
            "w_down": ParamSpec((f, d), ("mlp", "embed")),
        }
    return {
        "w_up": ParamSpec((d, f), ("embed", "mlp")),
        "w_down": ParamSpec((f, d), ("mlp", "embed")),
    }


def mlp_apply(p: dict, x: jnp.ndarray, cfg: ModelConfig, axo=None) -> jnp.ndarray:
    """Dense FFN.  ``axo`` = (AxODeployment, entries) runs each projection on
    the approximate operator's cached weight factors (activations stay exact)."""
    ent = axo[1] if axo is not None else {}

    def lin(name, v):
        if name in ent:
            return axo[0].apply(v, ent[name])
        return v @ p[name]

    with jax.named_scope("mlp"):
        if cfg.act == "swiglu":
            h = jax.nn.silu(lin("w_gate", x)) * lin("w_up", x)
        else:
            h = jax.nn.gelu(lin("w_up", x))
        return lin("w_down", h)


def embed_spec(cfg: ModelConfig) -> dict:
    out = {"tok": ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=1.0)}
    if not cfg.tie_embeddings:
        out["unembed"] = ParamSpec((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    return out
