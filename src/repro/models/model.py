"""Model assembly: spec trees, caches, forward (train / prefill / decode), loss.

A model is ``embed -> stages -> final norm -> unembed``; each stage scans a
super-block of layers over ``repeats`` (single compiled block regardless of
depth), with optional remat.  Heterogeneous families are all expressed through
the super-block layer list:

  dense    [(attn, dense)]
  moe      [(attn|mla, moe)]  (+ leading dense stage for DeepSeek-V3)
  ssm      [(mamba, none)]
  hybrid   jamba 8-layer block: 7 mamba + 1 attn, alternating dense/moe MLPs
  encdec   whisper: encoder stage of (attn_nc, dense) + decoder (attn_x, dense)
  vlm      5-layer block: 4 (attn, dense) + 1 (xattn, dense)

Caches are fixed-capacity, stacked over ``repeats`` so the same scan drives
decode.  Modality frontends are STUBS by assignment: whisper consumes
precomputed frame embeddings, the VLM precomputed patch embeddings.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, StageConfig
from .attention import (
    attn_apply,
    attn_spec,
    mla_apply,
    mla_spec,
    xattn_apply,
    xattn_kv,
    xattn_spec,
)
from .layers import embed_spec, mlp_apply, mlp_spec, rmsnorm, sinusoid_pos
from .moe import moe_apply, moe_spec
from .sharding import ShardingRules, constrain
from .spec import ParamSpec, stacked
from .ssm import mamba_apply, mamba_decode, mamba_dims, mamba_spec

__all__ = [
    "model_spec",
    "cache_spec",
    "forward",
    "compute_loss",
    "HAS_CACHE",
]

# Which mixer kinds carry decode state.
HAS_CACHE = {"attn": True, "attn_x": True, "xattn": True, "mla": True,
             "mamba": True, "attn_nc": False}


# ---------------------------------------------------------------------------
# Param spec tree
# ---------------------------------------------------------------------------


def _mixer_spec(cfg: ModelConfig, mixer: str) -> dict:
    if mixer in ("attn", "attn_nc"):
        return attn_spec(cfg)
    if mixer == "attn_x":                      # whisper decoder: self + cross
        return {
            "self": attn_spec(cfg),
            "cross": xattn_spec(cfg),
            "norm_x": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
        }
    if mixer == "xattn":
        return xattn_spec(cfg)
    if mixer == "mla":
        return mla_spec(cfg)
    if mixer == "mamba":
        return mamba_spec(cfg)
    raise ValueError(f"unknown mixer {mixer!r}")


def _mlp_spec(cfg: ModelConfig, mlp: str) -> dict | None:
    if mlp == "dense":
        return mlp_spec(cfg)
    if mlp == "moe":
        return moe_spec(cfg)
    if mlp == "none":
        return None
    raise ValueError(f"unknown mlp {mlp!r}")


def _layer_spec(cfg: ModelConfig, mixer: str, mlp: str) -> dict:
    out = {
        "norm1": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
        "mixer": _mixer_spec(cfg, mixer),
    }
    ms = _mlp_spec(cfg, mlp)
    if ms is not None:
        out["norm2"] = ParamSpec((cfg.d_model,), ("embed",), init="ones")
        out["mlp"] = ms
    return out


def _stage_spec(cfg: ModelConfig, stage: StageConfig) -> dict:
    block = {str(i): _layer_spec(cfg, mixer, mlp) for i, (mixer, mlp) in enumerate(stage.layers)}
    return jax.tree.map(
        lambda s: stacked(s, stage.repeats), block,
        is_leaf=lambda s: isinstance(s, ParamSpec),
    )


def model_spec(cfg: ModelConfig) -> dict:
    out = {"embed": embed_spec(cfg)}
    out["stages"] = {str(i): _stage_spec(cfg, s) for i, s in enumerate(cfg.stages)}
    out["norm_f"] = ParamSpec((cfg.d_model,), ("embed",), init="ones")
    if cfg.encoder is not None:
        enc_stage = StageConfig(repeats=cfg.encoder.n_layers, layers=(("attn_nc", "dense"),))
        out["encoder"] = {
            "stage": _stage_spec(cfg, enc_stage),
            "norm_f": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
        }
    if cfg.mtp:
        d = cfg.d_model
        out["mtp"] = {
            "norm_h": ParamSpec((d,), ("embed",), init="ones"),
            "norm_e": ParamSpec((d,), ("embed",), init="ones"),
            "proj": ParamSpec((2 * d, d), (None, "embed")),
        }
    return out


# ---------------------------------------------------------------------------
# Cache spec tree
# ---------------------------------------------------------------------------


def _layer_cache_spec(
    cfg: ModelConfig, mixer: str, batch: int, max_seq: int, enc_len: int
) -> dict | None:
    g, hd = cfg.kv_heads, cfg.resolved_head_dim
    kv_axes = ("batch", "kv_seq", "kv_heads", "head_dim")
    enc_axes = ("batch", "kv_enc", "kv_heads", "head_dim")
    if mixer == "attn":
        return {
            "k": ParamSpec((batch, max_seq, g, hd), kv_axes, init="zeros"),
            "v": ParamSpec((batch, max_seq, g, hd), kv_axes, init="zeros"),
        }
    if mixer == "attn_x":
        return {
            "k": ParamSpec((batch, max_seq, g, hd), kv_axes, init="zeros"),
            "v": ParamSpec((batch, max_seq, g, hd), kv_axes, init="zeros"),
            "xk": ParamSpec((batch, enc_len, g, hd), enc_axes, init="zeros"),
            "xv": ParamSpec((batch, enc_len, g, hd), enc_axes, init="zeros"),
        }
    if mixer == "xattn":
        return {
            "xk": ParamSpec((batch, enc_len, g, hd), enc_axes, init="zeros"),
            "xv": ParamSpec((batch, enc_len, g, hd), enc_axes, init="zeros"),
        }
    if mixer == "mla":
        m = cfg.mla
        return {
            "ckv": ParamSpec((batch, max_seq, m.kv_lora_rank),
                             ("batch", "kv_seq", "lora"), init="zeros"),
            "kpe": ParamSpec((batch, max_seq, m.rope_head_dim),
                             ("batch", "kv_seq", None), init="zeros"),
        }
    if mixer == "mamba":
        s = cfg.ssm
        dims = mamba_dims(cfg)
        return {
            "conv": ParamSpec((batch, s.d_conv - 1, dims["conv_dim"]),
                              ("batch", None, "ssm_inner"), init="zeros"),
            "state": ParamSpec(
                (batch, dims["n_heads"], s.head_dim, s.d_state),
                ("batch", "ssm_heads", None, None), init="zeros", dtype="float32",
            ),
        }
    return None


def cache_spec(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    """Spec tree for the decode cache (same nesting as the param stages tree)."""
    enc_len = cfg.encoder.n_ctx if cfg.encoder is not None else cfg.n_img_tokens
    out = {}
    for si, stage in enumerate(cfg.stages):
        blk = {}
        for i, (mixer, _) in enumerate(stage.layers):
            c = _layer_cache_spec(cfg, mixer, batch, max_seq, enc_len)
            if c is not None:
                blk[str(i)] = c
        out[str(si)] = jax.tree.map(
            lambda s: stacked(s, stage.repeats), blk,
            is_leaf=lambda s: isinstance(s, ParamSpec),
        )
    return out


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------


def _apply_layer(
    mixer: str,
    mlp: str,
    p: dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    rules: ShardingRules,
    ctx: dict,
    cache: dict | None,
    axo_layer: dict | None = None,
):
    """Pre-norm residual layer.  Returns (x, aux_delta, new_cache).

    ``axo_layer`` is this layer's entry dict from an ``AxODeployment``
    (``ctx["axo"]``): when present, the named projections run through the
    approximate operator's cached weight factors instead of exact matmuls.
    """
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    new_cache = None
    use_rope = cfg.pos_encoding == "rope"
    dep = ctx.get("axo")

    def ax(part, sub=None):
        if dep is None or not axo_layer or part not in axo_layer:
            return None
        ent = axo_layer[part]
        if sub is not None:
            ent = ent.get(sub) if isinstance(ent, dict) else None
            if ent is None:
                return None
        return (dep, ent)

    if mixer in ("attn", "attn_nc"):
        attn_cache = None
        if cache is not None and mixer == "attn":
            attn_cache = {"k": cache["k"], "v": cache["v"]}
        out, nc = attn_apply(
            p["mixer"], h, cfg, rules,
            positions=ctx["positions"], causal=(mixer == "attn"),
            use_rope=use_rope and mixer == "attn",
            cache=attn_cache, cache_index=ctx["cache_index"],
            q_start=ctx["q_start"], axo=ax("mixer"),
        )
        if nc is not None:
            new_cache = nc
    elif mixer == "attn_x":
        self_cache = None
        if cache is not None:
            self_cache = {"k": cache["k"], "v": cache["v"]}
        out, nc = attn_apply(
            p["mixer"]["self"], h, cfg, rules,
            positions=ctx["positions"], causal=True, use_rope=use_rope,
            cache=self_cache, cache_index=ctx["cache_index"],
            q_start=ctx["q_start"], axo=ax("mixer", "self"),
        )
        x = x + out
        h = rmsnorm(x, p["mixer"]["norm_x"], cfg.norm_eps)
        if ctx["enc_out"] is not None:
            kv = xattn_kv(p["mixer"]["cross"], ctx["enc_out"],
                          axo=ax("mixer", "cross"))
        else:
            kv = (cache["xk"], cache["xv"])
        out = xattn_apply(p["mixer"]["cross"], h, cfg, rules, kv=kv,
                          axo=ax("mixer", "cross"))
        if nc is not None:
            new_cache = dict(nc)
            if ctx["enc_out"] is not None:
                new_cache["xk"], new_cache["xv"] = (
                    kv[0].astype(cache["xk"].dtype), kv[1].astype(cache["xv"].dtype))
            else:
                new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
    elif mixer == "xattn":
        if ctx["enc_out"] is not None:
            kv = xattn_kv(p["mixer"], ctx["enc_out"], axo=ax("mixer"))
        else:
            kv = (cache["xk"], cache["xv"])
        out = xattn_apply(p["mixer"], h, cfg, rules, kv=kv, gated=True,
                          axo=ax("mixer"))
        if cache is not None:
            if ctx["enc_out"] is not None:
                new_cache = {"xk": kv[0].astype(cache["xk"].dtype),
                             "xv": kv[1].astype(cache["xv"].dtype)}
            else:
                new_cache = {"xk": cache["xk"], "xv": cache["xv"]}
    elif mixer == "mla":
        mla_cache = None
        if cache is not None:
            mla_cache = {"ckv": cache["ckv"], "kpe": cache["kpe"]}
        out, nc = mla_apply(
            p["mixer"], h, cfg, rules,
            positions=ctx["positions"], cache=mla_cache, cache_index=ctx["cache_index"],
            q_start=ctx["q_start"], axo=ax("mixer"),
        )
        if nc is not None:
            new_cache = nc
    elif mixer == "mamba":
        if ctx["mode"] == "decode":
            out, (conv, state) = mamba_decode(
                p["mixer"], h, cfg, rules, cache["conv"], cache["state"],
                axo=ax("mixer"))
            new_cache = {"conv": conv, "state": state}
        else:
            out, (conv, state) = mamba_apply(p["mixer"], h, cfg, rules,
                                             axo=ax("mixer"))
            if cache is not None:
                new_cache = {"conv": conv.astype(cache["conv"].dtype), "state": state}
    else:
        raise ValueError(f"unknown mixer {mixer!r}")

    x = x + out
    aux = jnp.zeros((), jnp.float32)
    if mlp != "none":
        h = rmsnorm(x, p["norm2"], cfg.norm_eps)
        if mlp == "moe":
            out, aux = moe_apply(p["mlp"], h, cfg, rules, axo=ax("mlp"))
        else:
            out = mlp_apply(p["mlp"], h, cfg, axo=ax("mlp"))
        x = x + out
    x = constrain(x, rules, "batch", "res_seq", "embed")
    return x, aux, new_cache


def _run_stage(
    stage_params: dict,
    stage: StageConfig,
    x: jnp.ndarray,
    cfg: ModelConfig,
    rules: ShardingRules,
    ctx: dict,
    cache: dict | None,
    axo_stage: dict | None = None,
):
    """Scan the super-block over ``repeats``.  Returns (x, aux, new_cache).

    ``axo_stage`` (AxODeployment entries, stacked over ``repeats`` like the
    params) rides through the scan as a third xs element.
    """
    layers = stage.layers

    def block(carry, xs):
        x, aux = carry
        p_blk, c_blk, a_blk = xs
        new_c = {}
        for i, (mixer, mlp) in enumerate(layers):
            li = str(i)
            lc = c_blk.get(li) if c_blk else None
            la = a_blk.get(li) if a_blk else None
            x, da, nc = _apply_layer(
                mixer, mlp, p_blk[li], x, cfg, rules, ctx, lc, axo_layer=la
            )
            aux = aux + da
            if nc is not None:
                new_c[li] = nc
        return (x, aux), new_c

    body = jax.checkpoint(block) if (cfg.remat and ctx["mode"] == "train") else block
    carry0 = (x, jnp.zeros((), jnp.float32))
    xs = (stage_params, cache if cache else {}, axo_stage if axo_stage else {})
    # the scan's own work (each layer's slice of the stacked weights, cache
    # and AxO tables, the new cache's stacking) and what no sublayer scope
    # names (norms, residual adds) reads as "layers" in a device trace
    with jax.named_scope("layers"):
        if cfg.unroll_loops:
            # Cost-probe mode: Python loop so cost_analysis counts every repeat.
            carry = carry0
            ys = []
            for r in range(stage.repeats):
                carry, y = body(carry, jax.tree.map(lambda t: t[r], xs))
                ys.append(y)
            (x, aux) = carry
            new_cache = (
                jax.tree.map(lambda *t: jnp.stack(t), *ys) if ys and ys[0] else {}
            )
        else:
            (x, aux), new_cache = jax.lax.scan(body, carry0, xs)
    return x, aux, (new_cache if new_cache else None)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _encode(params: dict, cfg: ModelConfig, rules: ShardingRules,
            enc_embeds: jnp.ndarray, mode: str, axo=None):
    """Whisper-style encoder over precomputed frame embeddings (stub frontend).

    ``mode`` must follow the outer pass: in training the encoder layers remat
    like the decoder's (without this the 24-layer encoder saves every forward
    intermediate for backward -- measured ~15 GB/device at train_4k)."""
    x = enc_embeds
    if cfg.pos_encoding == "sinusoid":
        x = x + sinusoid_pos(
            jnp.arange(x.shape[1], dtype=jnp.int32), cfg.d_model
        ).astype(x.dtype)[None]
    enc_stage = StageConfig(repeats=cfg.encoder.n_layers, layers=(("attn_nc", "dense"),))
    ctx = {
        "mode": mode,
        "positions": jnp.arange(x.shape[1], dtype=jnp.int32),
        "cache_index": None,
        "enc_out": None,
        "q_start": 0,
        "axo": axo,
    }
    x, _, _ = _run_stage(
        params["encoder"]["stage"], enc_stage, x, cfg, rules, ctx, None,
        axo_stage=axo.encoder if axo is not None else None,
    )
    return rmsnorm(x, params["encoder"]["norm_f"], cfg.norm_eps)


def forward(
    params: dict,
    cfg: ModelConfig,
    rules: ShardingRules,
    tokens: jnp.ndarray,                  # (B, S) int32
    *,
    mode: str = "train",                  # train | prefill | decode
    cache: dict | None = None,
    cache_index: jnp.ndarray | None = None,
    enc_embeds: jnp.ndarray | None = None,   # (B, n_ctx, d) whisper stub frontend
    img_embeds: jnp.ndarray | None = None,   # (B, n_img, d) VLM stub frontend
    axo=None,                                # optional axo.deploy.AxODeployment
):
    """Returns (hidden (B,S,d) or last-step hidden for prefill, aux, new_cache)."""
    b, s = tokens.shape
    if cache_index is None:
        cache_index = jnp.zeros((), jnp.int32)
    positions = cache_index + jnp.arange(s, dtype=jnp.int32)

    x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    x = constrain(x, rules, "batch", "res_seq", "embed")
    if cfg.pos_encoding == "sinusoid":
        x = x + sinusoid_pos(positions, cfg.d_model).astype(x.dtype)[None]

    enc_out = None
    if cfg.encoder is not None and enc_embeds is not None:
        enc_out = _encode(params, cfg, rules, enc_embeds, mode, axo)
    elif cfg.n_img_tokens and img_embeds is not None:
        enc_out = img_embeds

    ctx = {
        "mode": mode,
        "positions": positions,
        "cache_index": None if cache is None else cache_index,
        "enc_out": enc_out,
        # static position of query row 0: known (0) for train and from-scratch
        # prefill; unknown for decode (direct path anyway)
        "q_start": 0 if mode in ("train", "prefill") else None,
        "axo": axo,
    }

    aux = jnp.zeros((), jnp.float32)
    new_cache = {} if cache is not None else None
    for si, stage in enumerate(cfg.stages):
        sc = cache.get(str(si)) if cache is not None else None
        sa = axo.stages.get(str(si)) if axo is not None else None
        x, da, nc = _run_stage(
            params["stages"][str(si)], stage, x, cfg, rules, ctx, sc,
            axo_stage=sa,
        )
        aux = aux + da
        if new_cache is not None:
            new_cache[str(si)] = nc if nc is not None else {}

    x = rmsnorm(x, params["norm_f"], cfg.norm_eps)
    return x, aux, new_cache


def _unembed(params: dict, cfg: ModelConfig, rules: ShardingRules, x: jnp.ndarray,
             axo=None):
    with jax.named_scope("head"):
        if axo is not None and axo.head is not None:
            logits = axo.apply(x, axo.head)
        elif cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]["tok"])
        else:
            logits = x @ params["embed"]["unembed"]
        return constrain(logits, rules, "batch", "res_seq", "vocab")


def logits_fn(params, cfg, rules, x, axo=None):
    return _unembed(params, cfg, rules, x, axo=axo)


def _masked_ce(logits: jnp.ndarray, labels: jnp.ndarray):
    """Mean CE over labels >= 0.  logits (B,S,V), labels (B,S) int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    nll = lse - tgt
    mask = (labels >= 0).astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def compute_loss(
    params: dict,
    cfg: ModelConfig,
    rules: ShardingRules,
    batch: dict,
):
    """Training loss: CE + MoE aux (+ optional DeepSeek-style MTP head loss).

    ``batch``: {"tokens": (B,S), "labels": (B,S)} (+ "enc_embeds"/"img_embeds").
    """
    x, aux, _ = forward(
        params, cfg, rules, batch["tokens"], mode="train",
        enc_embeds=batch.get("enc_embeds"), img_embeds=batch.get("img_embeds"),
    )
    logits = _unembed(params, cfg, rules, x)
    ce = _masked_ce(logits, batch["labels"])
    loss = ce + aux
    metrics = {"ce": ce, "moe_aux": aux}

    if cfg.mtp:
        # DeepSeek-V3-style multi-token prediction: merge hidden state t with the
        # embedding of token t+1, predict label t+1 (i.e. token t+2).
        emb_next = jnp.take(params["embed"]["tok"], batch["tokens"][:, 1:], axis=0)
        h = jnp.concatenate(
            [
                rmsnorm(x[:, :-1], params["mtp"]["norm_h"], cfg.norm_eps),
                rmsnorm(emb_next, params["mtp"]["norm_e"], cfg.norm_eps),
            ],
            axis=-1,
        )
        h = h @ params["mtp"]["proj"]
        mtp_logits = _unembed(params, cfg, rules, h)
        mtp_ce = _masked_ce(mtp_logits, batch["labels"][:, 1:])
        loss = loss + cfg.mtp_weight * mtp_ce
        metrics["mtp_ce"] = mtp_ce

    metrics["loss"] = loss
    return loss, metrics
