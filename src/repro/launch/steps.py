"""Step builders: train / prefill / decode, plus sharding trees for jit.

These are the functions the dry-run lowers and the drivers execute.  All of
them close over (cfg, rules) and take only arrays, so ``jax.jit(fn).lower()``
with ShapeDtypeStructs never allocates.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from ..models.model import cache_spec, compute_loss, forward, logits_fn
from ..models.sharding import ShardingRules, named_sharding
from ..models.spec import abstract_params, init_params, param_shardings
from ..optim import Optimizer, apply_updates, clip_by_global_norm
from ..optim.compress import compress_int8, decompress_int8

__all__ = [
    "make_train_step",
    "make_prefill_step",
    "make_decode_step",
    "train_state_shardings",
    "batch_shardings",
    "cache_shardings",
    "abstract_cache",
    "init_cache",
]


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------


def _split_microbatches(batch: dict, accum: int) -> dict:
    def split(x):
        b = x.shape[0]
        assert b % accum == 0, f"batch {b} not divisible by accum {accum}"
        return x.reshape(accum, b // accum, *x.shape[1:])

    return jax.tree.map(split, batch)


def make_train_step(
    cfg: ModelConfig,
    rules: ShardingRules,
    opt: Optimizer,
    accum_steps: int = 1,
    clip_norm: float = 1.0,
    int8_accum: bool = False,
):
    """(params, opt_state, step, batch) -> (params, opt_state, metrics).

    ``accum_steps > 1`` runs microbatched gradient accumulation via lax.scan;
    ``int8_accum`` stores the accumulator int8 + error feedback (4x less HBM).
    """

    def loss_fn(params, mb):
        return compute_loss(params, cfg, rules, mb)

    def train_step(params, opt_state, step, batch):
        if accum_steps == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
        else:
            mbs = _split_microbatches(batch, accum_steps)

            def one_mb(carry, mb):
                (loss_aux, metrics_aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb
                )
                if int8_accum:
                    # accumulate in an fp32 view, re-compress with error feedback
                    acc_q, acc_s, err = carry
                    gl, tdef = jax.tree.flatten(g)
                    ql = tdef.flatten_up_to(acc_q)
                    sl = tdef.flatten_up_to(acc_s)
                    el = tdef.flatten_up_to(err)
                    qs, ss, es = [], [], []
                    for gi, qa, sa, ei in zip(gl, ql, sl, el):
                        tot = decompress_int8(qa, sa) + gi.astype(jnp.float32)
                        q, s, e = compress_int8(tot, ei)
                        qs.append(q)
                        ss.append(s)
                        es.append(e)
                    carry = (
                        tdef.unflatten(qs), tdef.unflatten(ss), tdef.unflatten(es)
                    )
                else:
                    carry = jax.tree.map(
                        lambda a, gi: a + gi.astype(jnp.float32), carry, g
                    )
                return carry, (loss_aux, metrics_aux)

            if int8_accum:
                zero_q = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.int8), params)
                zero_s = jax.tree.map(lambda p: jnp.ones((), jnp.float32), params)
                zero_e = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (acc_q, acc_s, _), (losses, metrics_s) = jax.lax.scan(
                    one_mb, (zero_q, zero_s, zero_e), mbs
                )
                grads = jax.tree.map(
                    lambda q, s: decompress_int8(q, s) / accum_steps, acc_q, acc_s
                )
            else:
                zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
                acc, (losses, metrics_s) = jax.lax.scan(one_mb, zero, mbs)
                grads = jax.tree.map(lambda a: a / accum_steps, acc)
            loss = losses.mean()
            metrics = jax.tree.map(lambda m: m.mean(), metrics_s)

        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        updates, opt_state = opt.update(grads, opt_state, params, step)
        params = apply_updates(params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def abstract_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    return abstract_params(cache_spec(cfg, batch, max_seq), dtype=dtype)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    return init_params(cache_spec(cfg, batch, max_seq), dtype=dtype)  # all zeros


def make_prefill_step(cfg: ModelConfig, rules: ShardingRules, max_seq: int):
    """(params, tokens[, frontend embeds][, axo=]) -> (last-position logits, cache).

    ``frontend`` is the stubbed modality input -- frame embeddings for the
    enc-dec family, patch embeddings for the VLM family (cfg decides which).
    The cache is created inside the step (zeros) at capacity ``max_seq`` and
    filled by the prefill pass -- one compiled program per (batch, capacity).

    ``axo`` (an ``axo.deploy.AxODeployment``, a pytree) is a step argument:
    the compiled step serves every token through the approximate operator
    from the deployment's cached weight codes/factors, with no per-call
    requantization and without embedding them in the program.  Bind it with
    ``functools.partial(jax.jit(step), axo=dep)``.
    """

    def prefill_step(params, tokens, frontend=None, axo=None):
        from ..obs.telemetry import note_trace

        note_trace("launch.prefill_step")  # runs once per (re)trace
        b = tokens.shape[0]
        cache = init_cache(cfg, b, max_seq, dtype=params["norm_f"].dtype)
        enc = frontend if cfg.encoder is not None else None
        img = frontend if cfg.n_img_tokens else None
        x, _, cache = forward(
            params, cfg, rules, tokens, mode="prefill",
            cache=cache, cache_index=jnp.zeros((), jnp.int32),
            enc_embeds=enc, img_embeds=img, axo=axo,
        )
        logits = logits_fn(params, cfg, rules, x[:, -1:], axo=axo)
        return logits, cache

    return prefill_step


def make_decode_step(cfg: ModelConfig, rules: ShardingRules):
    """(params, cache, tokens (B,1), index ()[, axo=]) -> (logits (B,1,V),
    new cache).  ``axo`` as in :func:`make_prefill_step`."""

    def decode_step(params, cache, tokens, index, axo=None):
        from ..obs.telemetry import note_trace

        note_trace("launch.decode_step")  # runs once per (re)trace
        x, _, cache = forward(
            params, cfg, rules, tokens, mode="decode",
            cache=cache, cache_index=index, axo=axo,
        )
        logits = logits_fn(params, cfg, rules, x, axo=axo)
        return logits, cache

    return decode_step


# ---------------------------------------------------------------------------
# Sharding trees
# ---------------------------------------------------------------------------


def train_state_shardings(cfg: ModelConfig, rules: ShardingRules, mesh, opt: Optimizer):
    """(param shardings, opt-state shardings) derived from the spec tree."""
    from ..models.model import model_spec

    spec = model_spec(cfg)
    p_sh = param_shardings(spec, rules, mesh)
    o_sh = param_shardings(opt.state_spec(spec), rules, mesh)
    return p_sh, o_sh


def batch_shardings(rules: ShardingRules, mesh, batch_specs: dict):
    """Data-input shardings: tokens/labels over batch; stub embeds likewise."""

    def sh(path_leaf):
        ndim = len(path_leaf.shape)
        axes = ("batch",) + (None,) * (ndim - 1)
        return named_sharding(mesh, rules.resolve(axes, kind="act"), path_leaf.shape)

    return jax.tree.map(sh, batch_specs)


def cache_shardings(cfg: ModelConfig, rules: ShardingRules, mesh, batch: int, max_seq: int):
    return param_shardings(cache_spec(cfg, batch, max_seq), rules, mesh, kind="act")
