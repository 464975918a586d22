"""Plain float32 reference of the Granite 4.0 hybrid decoder, teacher-forced.

Imports nothing of the program.  It follows the configuration file: token
embedding times ``embedding_multiplier``; per layer, by ``layer_types``,
either a pre-RMSNorm Mamba-2 mixer or a pre-RMSNorm GQA attention without
positions (scores scaled by ``attention_multiplier``, causal), then a
pre-RMSNorm SiLU-gated MLP, each branch added times ``residual_multiplier``;
a final RMSNorm and the tied embedding as head, divided by
``logits_scaling``.

The Mamba-2 mixer, as published for ``mamba_n_groups`` 1: ``in_proj`` splits
into the gate z, the conv input xBC and dt; a causal depthwise conv of
``mamba_d_conv`` taps with bias over xBC, then SiLU, splits into x (heads of
``mamba_d_head``), B and C (``mamba_d_state`` each); dt = softplus(dt +
dt_bias), A = -exp(A_log); then, position by position, the recurrence
h = exp(dt A) h + dt B x^T, y = C h + D x; the gated RMSNorm of y * silu(z)
at ``rms_norm_eps``; ``out_proj``.  The recurrence runs sequentially over
positions (a scan), not in chunks.

Every matmul runs in float32 at HIGHEST precision, one layer at a time
(each layer a jitted call that upcasts its own weights), so only one layer's
float32 copy is alive at a time.  With ``axo`` the projections of the groups
it names ("attn": q/k/v/o, "ssm": in_proj/out_proj) run through the
approximate multiplier as ``granite-3-2b.py`` beside this file defines it;
``precision="fp8"`` rounds every other linear layer's operands to float8, as
there.
"""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
GROUPS = {"wq": "attn", "wk": "attn", "wv": "attn", "wo": "attn",
          "in_proj": "ssm", "out_proj": "ssm"}


def _granite_ref():
    path = Path(__file__).with_name("granite-3-2b.py")
    spec = importlib.util.spec_from_file_location("bench_ref_granite_3_2b", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


G = _granite_ref()


def make_layers(config: dict, axo: dict | None, precision: str, n_prompt: int):
    """jitted (x, stacked layer weights, repeat) -> x for each layer kind."""
    if config["mamba_n_groups"] != 1:
        raise ValueError("the reference writes the mixer for mamba_n_groups 1")
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    g = config["num_key_value_heads"]
    hd = d // h
    eps = config["rms_norm_eps"]
    res = config["residual_multiplier"]
    inner = config["mamba_expand"] * d
    n_state = config["mamba_d_state"]
    p_head = config["mamba_d_head"]
    tabs = None
    if axo is not None:
        vals, f, gt = G.operator_tables(axo["lut_config"], int(axo["rank"]))
        tabs = (jnp.asarray(vals), jnp.asarray(f), jnp.asarray(gt))

    def linear(x, w, name):
        if tabs is not None and GROUPS.get(name) in axo["layers"]:
            return G._axo_linear(x, w, tabs, n_prompt)
        if precision == "fp8":
            return G._fp8_linear(x, w)
        return jnp.einsum("bsk,kn->bsn", x, w, precision=HI)

    def mlp(x, lw):
        hn = G._rmsnorm(x, lw["norm2"], eps)
        m = jax.nn.silu(linear(hn, lw["w_gate"], "w_gate")) * linear(
            hn, lw["w_up"], "w_up")
        return x + res * linear(m, lw["w_down"], "w_down")

    def take(stacked, r):
        return jax.tree.map(lambda a: a[r].astype(jnp.float32), stacked)

    @jax.jit
    def attention(x, stacked, r):
        lw = take(stacked, r)
        b, s, _ = x.shape
        pos = jnp.arange(s)
        hn = G._rmsnorm(x, lw["norm1"], eps)
        q = linear(hn, lw["wq"], "wq").reshape(b, s, g, h // g, hd)
        k = linear(hn, lw["wk"], "wk").reshape(b, s, g, hd)
        v = linear(hn, lw["wv"], "wv").reshape(b, s, g, hd)
        sc = jnp.einsum("bqgrk,bsgk->bgrqs", q, k, precision=HI)
        sc = sc * config["attention_multiplier"]
        sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bgrqs,bsgk->bqgrk", p, v, precision=HI).reshape(b, s, d)
        return mlp(x + res * linear(o, lw["wo"], "wo"), lw)

    @jax.jit
    def mamba(x, stacked, r):
        lw = take(stacked, r)
        b, s, _ = x.shape
        hn = G._rmsnorm(x, lw["norm1"], eps)
        zxbcdt = linear(hn, lw["in_proj"], "in_proj")
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner: 2 * inner + 2 * n_state]
        dt = zxbcdt[..., 2 * inner + 2 * n_state:]               # (B, S, H)
        taps = lw["conv_w"].shape[0]
        padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = sum(padded[:, i: i + s] * lw["conv_w"][i] for i in range(taps))
        xbc = jax.nn.silu(conv + lw["conv_b"])
        xs = xbc[..., :inner].reshape(b, s, -1, p_head)           # (B, S, H, P)
        bm = xbc[..., inner: inner + n_state]                     # (B, S, N)
        cm = xbc[..., inner + n_state:]
        dt = jax.nn.softplus(dt + lw["dt_bias"])
        a = -jnp.exp(lw["a_log"])

        def step(state, inp):                                     # (B, H, P, N)
            x_t, dt_t, b_t, c_t = inp
            state = (state * jnp.exp(dt_t * a)[:, :, None, None]
                     + dt_t[:, :, None, None] * x_t[..., None] * b_t[:, None, None, :])
            y_t = jnp.einsum("bhpn,bn->bhp", state, c_t, precision=HI)
            return state, y_t

        state0 = jnp.zeros((b, xs.shape[2], p_head, n_state), jnp.float32)
        seq = (xs.swapaxes(0, 1), dt.swapaxes(0, 1), bm.swapaxes(0, 1),
               cm.swapaxes(0, 1))
        _, y = jax.lax.scan(step, state0, seq)
        y = y.swapaxes(0, 1) + xs * lw["d_skip"][:, None]         # (B, S, H, P)
        y = y.reshape(b, s, inner) * jax.nn.silu(z)
        y = G._rmsnorm(y, lw["norm"], eps)
        return mlp(x + res * linear(y, lw["out_proj"], "out_proj"), lw)

    return {"mamba": mamba, "attn": attention}


def served_logits(config: dict, weights: dict, prompts: np.ndarray,
                  served: np.ndarray, axo: dict | None = None,
                  precision: str = "float32"):
    """Logits (B, G, V) at the G positions that produced ``served`` tokens,
    teacher-forced over each prompt and its served tokens."""
    n_prompt = prompts.shape[1]
    tokens = jnp.asarray(np.concatenate([prompts, served[:, :-1]], axis=1),
                         jnp.int32)
    layers = make_layers(config, axo, precision, n_prompt)
    emb = weights["embed"]
    x = _embed(emb, tokens, config["embedding_multiplier"])
    for kind, stacked, r in weights["layers"]:
        x = layers[kind](x, stacked, r)
    return _head(x, weights["norm_f"], emb, n_prompt, config["rms_norm_eps"],
                 config["logits_scaling"], precision == "fp8")


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(emb, tokens, multiplier):
    return emb.astype(jnp.float32)[tokens] * multiplier


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _head(x, norm_f, emb, n_prompt, eps, scaling, fp8):
    x = x[:, n_prompt - 1:]   # the positions that produced a served token
    x = G._rmsnorm(x, norm_f.astype(jnp.float32), eps)
    emb = emb.astype(jnp.float32)
    if fp8:
        logits = G._fp8_linear(x, emb.T)
    else:
        logits = jnp.einsum("bsd,vd->bsv", x, emb, precision=HI)
    return logits / scaling
