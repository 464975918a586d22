"""Plain float32 reference of the Granite dense decoder, teacher-forced.

Imports nothing of the program.  It follows the configuration file:
token embedding times ``embedding_multiplier``; per layer, pre-RMSNorm GQA
attention with rotary embeddings (the two halves of each head rotated
against each other) and scores scaled by ``attention_multiplier``, then a
pre-RMSNorm SiLU-gated MLP, each branch added times ``residual_multiplier``;
a final RMSNorm and the tied embedding as head, divided by
``logits_scaling``.  Every matmul runs in float32 at HIGHEST precision, layer
by layer (a scan over the stacked layers, each upcast inside the step), so
only one layer's float32 copy is alive at a time.

With ``axo`` the attention projections run through the approximate
multiplier as the operator defines it: the product table is built from the
LUT bits by the operator reference beside this file, its error against the
exact product factored by SVD to rank R.  Activations and each weight matrix
are quantized per tensor, symmetric, to 8-bit codes; a projection is
``sx * sw * sum_k (a_k b_k + sum_r f_r[a_k] g_r[b_k])``.  An activation
tensor is one quantization group per call the server makes: the whole
prompt, then each decoded position.

``precision="fp8"`` is the control: every other linear layer's operands
rounded per tensor to float8 (e4m3), the step below the bfloat16 the
configuration states.
"""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ATTN = ("wq", "wk", "wv", "wo")


def _operator_ref():
    path = Path(__file__).with_name("mul8s.py")
    spec = importlib.util.spec_from_file_location("bench_ref_mul8s", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def operator_tables(lut_config: str, rank: int, n_bits: int = 8):
    """(signed values, f (2^n, R), g (2^n, R)) of the approximate multiplier."""
    ops = _operator_ref()
    bits = np.frombuffer(lut_config.encode(), np.uint8) - ord("0")
    table = ops.products(n_bits, bits[None])[0].astype(np.float64)
    v = ops._values(n_bits).astype(np.float64)
    u, s, vt = np.linalg.svd(table - np.outer(v, v))
    f = (u[:, :rank] * s[:rank]).astype(np.float32)
    g = vt[:rank].T.astype(np.float32)
    return v.astype(np.float32), f, g


def _rmsnorm(x, gamma, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * gamma


def _quant(x, axes, bits=8):
    """Symmetric per-tensor (over ``axes``) codes and scale."""
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True),
                        1e-12) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax - 1, qmax), scale


def _fp8(x):
    """Per-tensor scaled float8 (e4m3) rounding of ``x``, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _fp8_linear(x, w):
    return jnp.einsum("...k,kn->...n", _fp8(x), _fp8(w), precision=HI)



def _grouped_quant(x, n_prompt):
    """Codes and scales of (B, S, K) activations, one group for the prompt
    rows and one for each later position (each a call the server makes)."""
    s_head = jnp.max(jnp.abs(x[:, :n_prompt])) / 127
    s_tail = jnp.max(jnp.abs(x[:, n_prompt:]), axis=(0, 2)) / 127      # (S-P,)
    scale = jnp.concatenate(
        [jnp.full((n_prompt,), s_head), s_tail])[None, :, None]
    scale = jnp.maximum(scale, 1e-12 / 127)
    return jnp.clip(jnp.round(x / scale), -128, 127), scale


def _axo_linear(x, w, tabs, n_prompt):
    """x @ w through the approximate multiplier, from its definition."""
    vals, f, g = tabs
    xq, sx = _grouped_quant(x, n_prompt)
    wq, sw = _quant(w, (0, 1))
    xi = (xq.astype(jnp.int32) & 255)
    wi = (wq.astype(jnp.int32) & 255)
    exact = jnp.einsum("bsk,kn->bsn", vals[xi], vals[wi], precision=HI)
    err = jnp.einsum("bskr,knr->bsn", f[xi], g[wi], precision=HI)
    return (exact + err) * sx * sw[0, 0]


def make_forward(config: dict, axo: dict | None, precision: str, n_prompt: int):
    """jitted (weights, tokens (B, S)) -> float32 logits (B, S - P + 1, V)
    at the positions from the prompt's last on."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    g = config["num_key_value_heads"]
    hd = d // h
    eps = config["rms_norm_eps"]
    theta = config["rope_theta"]
    tabs = None
    if axo is not None:
        vals, f, gt = operator_tables(axo["lut_config"], int(axo["rank"]))
        tabs = (jnp.asarray(vals), jnp.asarray(f), jnp.asarray(gt))

    def linear(x, w, name):
        if tabs is not None and "attn" in axo["layers"] and name in ATTN:
            return _axo_linear(x, w, tabs, n_prompt)
        if precision == "fp8":
            return _fp8_linear(x, w)
        return jnp.einsum("bsk,kn->bsn", x, w, precision=HI)

    def rope(x, pos):
        inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = pos[:, None].astype(jnp.float32) * inv
        c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
        b, s, _ = x.shape
        pos = jnp.arange(s)
        hn = _rmsnorm(x, lw["norm1"], eps)
        q = rope(linear(hn, lw["wq"], "wq").reshape(b, s, h, hd), pos)
        k = rope(linear(hn, lw["wk"], "wk").reshape(b, s, g, hd), pos)
        v = linear(hn, lw["wv"], "wv").reshape(b, s, g, hd)
        q = q.reshape(b, s, g, h // g, hd)
        sc = jnp.einsum("bqgrk,bsgk->bgrqs", q, k, precision=HI)
        sc = sc * config["attention_multiplier"]
        sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bgrqs,bsgk->bqgrk", p, v, precision=HI).reshape(b, s, d)
        x = x + config["residual_multiplier"] * linear(o, lw["wo"], "wo")
        hn = _rmsnorm(x, lw["norm2"], eps)
        m = jax.nn.silu(linear(hn, lw["w_gate"], "w_gate")) * linear(
            hn, lw["w_up"], "w_up")
        x = x + config["residual_multiplier"] * linear(m, lw["w_down"], "w_down")
        return x, None

    @jax.jit
    def forward(weights, tokens):
        emb = weights["embed"].astype(jnp.float32)
        x = emb[tokens] * config["embedding_multiplier"]
        x, _ = jax.lax.scan(layer, x, weights["layers"])
        x = x[:, n_prompt - 1:]   # the positions that produced a served token
        x = _rmsnorm(x, weights["norm_f"].astype(jnp.float32), eps)
        if precision == "fp8":
            logits = _fp8_linear(x, emb.T)
        else:
            logits = jnp.einsum("bsd,vd->bsv", x, emb, precision=HI)
        return logits / config["logits_scaling"]

    return forward


def served_logits(config: dict, weights: dict, prompts: np.ndarray,
                  served: np.ndarray, axo: dict | None = None,
                  precision: str = "float32"):
    """Logits (B, G, V) at the G positions that produced ``served`` tokens,
    teacher-forced over each prompt and its served tokens."""
    n_prompt = prompts.shape[1]
    tokens = np.concatenate([prompts, served[:, :-1]], axis=1)
    fwd = make_forward(config, axo, precision, n_prompt)
    return fwd(weights, jnp.asarray(tokens, jnp.int32))
