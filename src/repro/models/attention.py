"""Attention: XLA-native blockwise (flash-equivalent) GQA, MLA, cross-attention.

The training/prefill path is *chunked* online-softmax attention (lax.scan over KV
blocks inside a map over Q blocks) so a 32k-token prefill never materializes an
(S x S) score matrix -- this is the XLA-level equivalent of the Pallas flash
kernel in ``repro.kernels.flash_attention_kernel`` (the TPU deployment path and
is validated against the same reference).  Decode (Sq == 1) uses direct softmax
over the cache.

Sharding notes (production meshes shard ``heads`` over the ``model`` axis):
KV is repeated group->heads *inside each KV chunk* so every attention einsum
carries a plain ``h`` dim; the repeat is chunk-local (bytes ~ kv_chunk) and lets
SPMD keep all score/accumulator tensors head-sharded with no (g, rep) reshape
ambiguity.

MLA (DeepSeek-V3) is implemented in its **absorbed / MQA-equivalent form**: the
latent cache ``c_kv`` acts as a single shared KV head of width
``kv_lora_rank (+ rope)``; q_nope is absorbed through ``wkv_b``'s K half and the
attention output is re-projected through its V half.  Expanded per-head K/V are
NEVER materialized -- this is what makes the 32k prefill / decode shapes fit, and
it matches how MLA is actually served.

Caches are fixed-capacity buffers updated with dynamic_update_slice, so one
compiled ``serve_step`` serves every position.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import rmsnorm
from .sharding import ShardingRules, constrain
from .spec import ParamSpec

__all__ = [
    "rope_cos_sin",
    "rope_rotate",
    "chunked_attention",
    "direct_attention",
    "attn_spec",
    "attn_apply",
    "mla_spec",
    "mla_apply",
    "xattn_spec",
    "xattn_kv",
    "xattn_apply",
]

NEG_INF = -1e30


@jax.custom_vjp
def _pinned(xs):
    """``optimization_barrier`` with a gradient rule.

    ``jax.lax.optimization_barrier`` has no differentiation rule, so using it on
    the training path raises ``NotImplementedError`` under ``grad``.  The barrier
    only constrains XLA scheduling -- mathematically it is the identity -- so the
    VJP passes cotangents straight through.  (No barrier on the backward pass:
    cotangents for integer leaves are ``float0`` placeholders that
    ``optimization_barrier`` cannot consume, and the backward all-gathers are
    not the ones being pinned.)
    """
    return jax.lax.optimization_barrier(xs)


def _pinned_fwd(xs):
    return _pinned(xs), None


def _pinned_bwd(_, g):
    return (g,)


_pinned.defvjp(_pinned_fwd, _pinned_bwd)


def rope_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float):
    """positions (..., S) int -> cos, sin (..., S, head_dim//2), computed on the fly."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def rope_rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x (..., S, H, hd); cos/sin (..., S, hd//2)."""
    hd = x.shape[-1]
    c = cos[..., None, :]
    s = sin[..., None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _repeat_kv(k: jnp.ndarray, h: int) -> jnp.ndarray:
    """(..., G, hd) -> (..., H, hd) by repeating each group H/G times."""
    g = k.shape[-2]
    if g == h:
        return k
    return jnp.repeat(k, h // g, axis=-2)


def chunked_attention(
    q: jnp.ndarray,                 # (B, Sq, H, hd)
    k: jnp.ndarray,                 # (B, Skv, G, hd)
    v: jnp.ndarray,                 # (B, Skv, G, hd_v)
    *,
    causal: bool,
    q_positions: jnp.ndarray,       # (Sq,) int32 absolute positions
    kv_len: jnp.ndarray | int,      # number of valid kv entries
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    scale: float | None = None,
    unroll: bool = False,
    q_start: int | None = None,
) -> jnp.ndarray:
    """Online-softmax blockwise attention; fp32 accumulators; O(Sq*hd) memory.

    ``unroll=True`` replaces the scan/map with Python loops (identical math) so
    cost probes see every block's FLOPs; never used on the execution path.

    ``q_start`` (static) enables **causal block skipping**: when the absolute
    position of query row 0 is known at trace time, each q block only scans the
    KV prefix it can attend to -- for nq = nk = n blocks this removes the
    n(n-1)/2 fully-masked upper-triangle block pairs (~48% of attention
    FLOPs/bytes at 32k prefill).  Masked-block results are bit-identical to the
    full scan (they contributed exp(-inf) = 0)."""
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    hd_v = v.shape[-1]
    scale = (1.0 / (hd ** 0.5)) if scale is None else scale

    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)

    qp = _pad_to(q, 1, q_chunk)
    qpos = _pad_to(q_positions, 0, q_chunk)
    sq_p = qp.shape[1]
    kp = _pad_to(k, 1, kv_chunk)
    vp = _pad_to(v, 1, kv_chunk)
    skv_p = kp.shape[1]
    kv_pos = jnp.arange(skv_p, dtype=jnp.int32)

    nq, nk = sq_p // q_chunk, skv_p // kv_chunk
    qp = qp.reshape(b, nq, q_chunk, h, hd).transpose(1, 0, 2, 3, 4)
    qpos = qpos.reshape(nq, q_chunk)
    kp = kp.reshape(b, nk, kv_chunk, k.shape[2], hd).transpose(1, 0, 2, 3, 4)
    vp = vp.reshape(b, nk, kv_chunk, v.shape[2], hd_v).transpose(1, 0, 2, 3, 4)
    kv_pos = kv_pos.reshape(nk, kv_chunk)

    kv_len = jnp.asarray(kv_len, jnp.int32)

    def one_q_block(args, n_kv: int | None = None):
        q_c, qpos_c = args  # (B, Qc, H, hd), (Qc,)

        @jax.checkpoint
        def body(carry, kv_c):
            m, l, acc = carry
            k_c, v_c, kvpos_c = kv_c
            kh = _repeat_kv(k_c, h)                 # chunk-local group->head repeat
            vh = _repeat_kv(v_c, h)
            s = jnp.einsum(
                "bqhk,bshk->bhqs", q_c, kh, preferred_element_type=jnp.float32
            ) * scale
            valid = kvpos_c[None, :] < kv_len
            if causal:
                valid = valid & (qpos_c[:, None] >= kvpos_c[None, :])
            s = jnp.where(valid[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqs,bshk->bhqk", p.astype(vh.dtype), vh,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        nkv = nk if n_kv is None else n_kv
        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, hd_v), jnp.float32)
        if unroll:
            carry = (m0, l0, a0)
            for j in range(nkv):
                carry, _ = body(carry, (kp[j], vp[j], kv_pos[j]))
            m, l, acc = carry
        elif n_kv is not None and n_kv < nk:
            # causal block skipping: a fori_loop over the FULL kv buffer with a
            # static trip count.  (Slicing xs per q block -- kp[:nkv] -- makes
            # sibling while loops with different tuple shapes, which trips an
            # XLA while-CSE bug under SPMD; with fori_loop every loop has
            # identical operands and only the bound constant differs.)
            def body_fori(j, carry):
                new_carry, _ = body(carry, (kp[j], vp[j], kv_pos[j]))
                return new_carry

            m, l, acc = jax.lax.fori_loop(0, nkv, body_fori, (m0, l0, a0))
        else:
            (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kp, vp, kv_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3)  # (B, Qc, H, hd_v)

    if causal and q_start is not None:
        # causal block skipping: q block i sees kv chunks [0, n_need(i)).
        # Pin the (gathered) KV buffers ONCE before the per-block loops --
        # otherwise XLA sinks a fresh seq all-gather into every loop body
        # (measured +50% all-gather bytes on a 4k train cell without this).
        kp, vp, kv_pos = _pinned((kp, vp, kv_pos))
        outs = []
        for i in range(nq):
            last_pos = q_start + (i + 1) * q_chunk - 1
            n_need = max(1, min(nk, last_pos // kv_chunk + 1))
            outs.append(one_q_block((qp[i], qpos[i]), n_kv=n_need))
        out = jnp.stack(outs)
    elif unroll:
        out = jnp.stack([one_q_block((qp[i], qpos[i])) for i in range(nq)])
    else:
        out = jax.lax.map(one_q_block, (qp, qpos))      # (nq, B, Qc, H, hd_v)
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, sq_p, h, hd_v)
    return out[:, :sq].astype(q.dtype)


def direct_attention(
    q: jnp.ndarray,                 # (B, Sq, H, hd) -- decode: Sq small
    k: jnp.ndarray,                 # (B, Skv, G, hd)
    v: jnp.ndarray,
    *,
    causal: bool,
    q_positions: jnp.ndarray,
    kv_len: jnp.ndarray | int,
    scale: float | None = None,
) -> jnp.ndarray:
    """Direct softmax attention over the whole KV; decode path (Sq tiny).

    Works with a seq-sharded KV cache: queries stay in grouped (g, rep) form so
    the KV is never repeated or gathered -- the score/weighted-value einsums
    reduce over the sharded seq dim, SPMD emits only small all-reduces of
    (B, H, Sq, *) tensors.  Decode rules replicate heads so nothing conflicts
    with the cache's seq sharding.
    """
    b, sq, h, hd = q.shape
    g = k.shape[2]
    rep = h // g
    scale = (1.0 / (hd ** 0.5)) if scale is None else scale
    qg = q.reshape(b, sq, g, rep, hd)
    s = jnp.einsum("bqgrk,bsgk->bgrqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    kv_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    valid = kv_pos[None, :] < jnp.asarray(kv_len, jnp.int32)
    if causal:
        valid = valid & (q_positions[:, None] >= kv_pos[None, :])
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bgrqs,bsgk->bqgrk", p, v, preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA self-attention layer
# ---------------------------------------------------------------------------


def attn_spec(cfg: ModelConfig) -> dict:
    d, h, g, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.resolved_head_dim
    return {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, g, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, g, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
    }


def attn_apply(
    p: dict,
    x: jnp.ndarray,                       # (B, S, d)
    cfg: ModelConfig,
    rules: ShardingRules,
    *,
    positions: jnp.ndarray,               # (S,) int32
    causal: bool = True,
    use_rope: bool = True,
    cache: dict | None = None,            # {'k','v'}: (B, Smax, G, hd)
    cache_index: jnp.ndarray | None = None,
    q_start: int | None = None,           # static row-0 position (causal skip)
    axo=None,                             # (AxODeployment, layer mixer entries)
):
    """Returns (out, new_cache).

    ``axo`` routes the q/k/v/o projections through the approximate operator's
    cached weight factors (attention *math* -- scores/softmax -- stays exact;
    AxO replaces multiplier arrays, i.e. the matmuls).
    """
    with jax.named_scope("attn.proj"):
        if axo is not None and "wq" in axo[1]:
            dep, ent = axo
            b_, s_ = x.shape[:2]
            h_, hd_ = p["wq"].shape[1], p["wq"].shape[2]
            g_ = p["wk"].shape[1]
            q = dep.apply(x, ent["wq"]).reshape(b_, s_, h_, hd_)
            k = dep.apply(x, ent["wk"]).reshape(b_, s_, g_, hd_)
            v = dep.apply(x, ent["wv"]).reshape(b_, s_, g_, hd_)
        else:
            q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
            k = jnp.einsum("bsd,dgk->bsgk", x, p["wk"])
            v = jnp.einsum("bsd,dgk->bsgk", x, p["wv"])
        q = constrain(q, rules, "batch", "seq", "heads", "head_dim")
        k = constrain(k, rules, "batch", "seq", "kv_heads", "head_dim")

    with jax.named_scope("attn.core"):
        if use_rope:
            cos, sin = rope_cos_sin(positions, cfg.resolved_head_dim, cfg.rope_theta)
            q = rope_rotate(q, cos, sin)
            k = rope_rotate(k, cos, sin)

    new_cache = None
    if cache is not None:
        with jax.named_scope("attn.kv_update"):
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1)
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        kv_len = cache_index + x.shape[1]
    else:
        kv_len = x.shape[1]

    with jax.named_scope("attn.core"):
        if x.shape[1] <= 4:  # decode path
            out = direct_attention(q, k, v, causal=causal, q_positions=positions,
                                   kv_len=kv_len)
        else:
            out = chunked_attention(
                q, k, v, causal=causal, q_positions=positions, kv_len=kv_len,
                q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
                unroll=cfg.unroll_loops,
                q_start=q_start if cfg.causal_block_skip else None,
            )
    with jax.named_scope("attn.proj"):
        if axo is not None and "wo" in axo[1]:
            dep, ent = axo
            out = dep.apply(out.reshape(*out.shape[:2], -1), ent["wo"])
        else:
            out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return constrain(out, rules, "batch", "seq", "embed"), new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 latent attention) -- absorbed / MQA-equivalent form
# ---------------------------------------------------------------------------


def mla_spec(cfg: ModelConfig) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qd = m.nope_head_dim + m.rope_head_dim
    return {
        "wq_a": ParamSpec((d, m.q_lora_rank), ("embed", "lora")),
        "q_norm": ParamSpec((m.q_lora_rank,), ("lora",), init="ones"),
        "wq_b": ParamSpec((m.q_lora_rank, h, qd), ("lora", "heads", "head_dim")),
        "wkv_a": ParamSpec((d, m.kv_lora_rank + m.rope_head_dim), ("embed", "lora")),
        "kv_norm": ParamSpec((m.kv_lora_rank,), ("lora",), init="ones"),
        "wkv_b": ParamSpec(
            (m.kv_lora_rank, h, m.nope_head_dim + m.v_head_dim),
            ("lora", "heads", "head_dim"),
        ),
        "wo": ParamSpec((h, m.v_head_dim, d), ("heads", "head_dim", "embed")),
    }


def mla_apply(
    p: dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    rules: ShardingRules,
    *,
    positions: jnp.ndarray,
    cache: dict | None = None,            # {'ckv': (B,Smax,r), 'kpe': (B,Smax,rope)}
    cache_index: jnp.ndarray | None = None,
    q_start: int | None = None,
    axo=None,                             # (AxODeployment, layer mixer entries)
):
    """Absorbed-form MLA.  The latent c_kv (+ shared rope key) is the entire KV:
    a single shared "KV head" of width r + rope; q_nope is absorbed through the
    K-half of wkv_b so scores live in latent space, and the attention output (in
    latent space) is re-projected through the V-half.  Softmax scale is that of
    the *unabsorbed* head width (nope + rope).

    With ``axo``, the plain last-dim linears (wq_a, wq_b, wkv_a, wo) run on the
    approximate operator; ``wkv_b`` stays exact -- its absorbed halves contract
    per-head against latents, not as a (K, N) linear.
    """
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    a_ent = axo[1] if axo is not None else {}

    def lin(name, fn_exact, v):
        if name in a_ent:
            return axo[0].apply(v, a_ent[name])
        return fn_exact(v)

    q = rmsnorm(lin("wq_a", lambda v: v @ p["wq_a"], x), p["q_norm"], cfg.norm_eps)
    if "wq_b" in a_ent:
        qd = m.nope_head_dim + m.rope_head_dim
        q = axo[0].apply(q, a_ent["wq_b"]).reshape(b, s, h, qd)
    else:
        q = jnp.einsum("bsr,rhk->bshk", q, p["wq_b"])
    q = constrain(q, rules, "batch", "seq", "heads", "head_dim")
    q_nope = q[..., : m.nope_head_dim]
    q_pe = q[..., m.nope_head_dim :]

    kv = lin("wkv_a", lambda v: v @ p["wkv_a"], x)
    ckv = rmsnorm(kv[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    kpe = kv[..., m.kv_lora_rank :][:, :, None, :]   # (B,S,1,rope) shared head

    cos, sin = rope_cos_sin(positions, m.rope_head_dim, cfg.rope_theta)
    q_pe = rope_rotate(q_pe, cos, sin)
    kpe = rope_rotate(kpe, cos, sin)[:, :, 0, :]

    # Absorb q_nope through wkv_b's K half: (B,S,H,nope) x (r,H,nope) -> (B,S,H,r)
    wk_half = p["wkv_b"][..., : m.nope_head_dim]          # (r, H, nope)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wk_half)
    q_full = jnp.concatenate([q_lat, q_pe], axis=-1)      # (B,S,H,r+rope)

    new_cache = None
    if cache is not None:
        cckv = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), cache_index, axis=1)
        ckpe = jax.lax.dynamic_update_slice_in_dim(
            cache["kpe"], kpe.astype(cache["kpe"].dtype), cache_index, axis=1)
        new_cache = {"ckv": cckv, "kpe": ckpe}
        ckv, kpe = cckv, ckpe
        kv_len = cache_index + s
    else:
        kv_len = s

    # Latent K and V: one shared head (MQA form).
    k_lat = jnp.concatenate([ckv, kpe], axis=-1)[:, :, None, :]  # (B,Skv,1,r+rope)
    v_lat = ckv[:, :, None, :]                                   # (B,Skv,1,r)
    att_scale = 1.0 / ((m.nope_head_dim + m.rope_head_dim) ** 0.5)

    if s <= 4 and cache is not None:
        ctx = direct_attention(
            q_full, k_lat, v_lat, causal=True, q_positions=positions,
            kv_len=kv_len, scale=att_scale,
        )
    else:
        ctx = chunked_attention(
            q_full, k_lat, v_lat, causal=True, q_positions=positions, kv_len=kv_len,
            q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk, scale=att_scale,
            unroll=cfg.unroll_loops,
            q_start=q_start if cfg.causal_block_skip else None,
        )
    # ctx: (B,S,H,r) in latent space; re-project through wkv_b's V half.
    wv_half = p["wkv_b"][..., m.nope_head_dim :]          # (r, H, v_hd)
    out = jnp.einsum("bshr,rhk->bshk", ctx, wv_half)
    if "wo" in a_ent:
        out = axo[0].apply(out.reshape(b, s, -1), a_ent["wo"])
    else:
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return constrain(out, rules, "batch", "seq", "embed"), new_cache


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder / VLM image layers)
# ---------------------------------------------------------------------------


def xattn_spec(cfg: ModelConfig) -> dict:
    d, h, g, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.resolved_head_dim
    return {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, g, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, g, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
        "gate": ParamSpec((1,), (None,), init="zeros"),   # VLM-style tanh gate
    }


def xattn_kv(p: dict, enc: jnp.ndarray, axo=None):
    """Precompute cross K/V from encoder/image states (cached for decode)."""
    if axo is not None and "wk" in axo[1]:
        dep, ent = axo
        b_, s_ = enc.shape[:2]
        g_, hd_ = p["wk"].shape[1], p["wk"].shape[2]
        k = dep.apply(enc, ent["wk"]).reshape(b_, s_, g_, hd_)
        v = dep.apply(enc, ent["wv"]).reshape(b_, s_, g_, hd_)
    else:
        k = jnp.einsum("bsd,dgk->bsgk", enc, p["wk"])
        v = jnp.einsum("bsd,dgk->bsgk", enc, p["wv"])
    return k, v


def xattn_apply(
    p: dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    rules: ShardingRules,
    *,
    kv: tuple[jnp.ndarray, jnp.ndarray],   # precomputed (k, v) from encoder states
    gated: bool = False,
    axo=None,                              # (AxODeployment, layer mixer entries)
):
    if axo is not None and "wq" in axo[1]:
        dep, ent = axo
        b_, s_ = x.shape[:2]
        h_, hd_ = p["wq"].shape[1], p["wq"].shape[2]
        q = dep.apply(x, ent["wq"]).reshape(b_, s_, h_, hd_)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q = constrain(q, rules, "batch", "seq", "heads", "head_dim")
    k, v = kv
    if x.shape[1] <= 4:
        out = direct_attention(
            q, k, v, causal=False,
            q_positions=jnp.arange(x.shape[1], dtype=jnp.int32),
            kv_len=k.shape[1],
        )
    else:
        out = chunked_attention(
            q, k, v, causal=False,
            q_positions=jnp.arange(x.shape[1], dtype=jnp.int32),
            kv_len=k.shape[1],
            q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
            unroll=cfg.unroll_loops,
        )
    if axo is not None and "wo" in axo[1]:
        dep, ent = axo
        out = dep.apply(out.reshape(*out.shape[:2], -1), ent["wo"])
    else:
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if gated:
        out = jnp.tanh(p["gate"].astype(out.dtype)) * out
    return constrain(out, rules, "batch", "seq", "embed")
