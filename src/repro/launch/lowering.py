"""Shared cell-lowering used by the dry-run and the cost probes."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig
from ..configs.registry import input_specs
from ..models.model import model_spec
from ..models.sharding import ShardingRules, named_sharding
from ..models.spec import abstract_params, param_shardings
from ..optim import cosine_schedule, make_optimizer
from .steps import (
    abstract_cache,
    batch_shardings,
    cache_shardings,
    make_decode_step,
    make_prefill_step,
    make_train_step,
)

__all__ = ["lower_step"]


def lower_step(cfg: ModelConfig, shape: ShapeConfig, mesh, rules: ShardingRules):
    """Lower the cell's step function from ShapeDtypeStructs (no allocation)."""
    spec = model_spec(cfg)
    params_abs = abstract_params(spec)
    p_sh = param_shardings(spec, rules, mesh)
    specs = input_specs(cfg, shape)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt = make_optimizer(cfg.optimizer, cosine_schedule(3e-4))
            o_spec = opt.state_spec(spec)
            opt_abs = abstract_params(o_spec)
            o_sh = param_shardings(o_spec, rules, mesh)
            b_sh = batch_shardings(rules, mesh, specs["batch"])
            step_abs = jax.ShapeDtypeStruct((), jnp.int32)
            fn = make_train_step(cfg, rules, opt)
            jitted = jax.jit(
                fn,
                in_shardings=(p_sh, o_sh, named_sharding(mesh, P()), b_sh),
                donate_argnums=(0, 1),
            )
            return jitted.lower(params_abs, opt_abs, step_abs, specs["batch"])
        if shape.kind == "prefill":
            fn = make_prefill_step(cfg, rules, max_seq=shape.seq_len)
            args = [params_abs, specs["tokens"]]
            shardings = [p_sh, batch_shardings(rules, mesh, specs["tokens"])]
            frontend = specs.get("enc_embeds", specs.get("img_embeds"))
            if frontend is not None:
                args.append(frontend)
                shardings.append(batch_shardings(rules, mesh, frontend))
            jitted = jax.jit(fn, in_shardings=tuple(shardings))
            return jitted.lower(*args)
        if shape.kind == "decode":
            cache_abs = abstract_cache(cfg, shape.global_batch, shape.seq_len)
            c_sh = cache_shardings(cfg, rules, mesh, shape.global_batch, shape.seq_len)
            fn = make_decode_step(cfg, rules)
            jitted = jax.jit(
                fn,
                in_shardings=(
                    p_sh, c_sh,
                    batch_shardings(rules, mesh, specs["tokens"]),
                    named_sharding(mesh, P()),
                ),
                donate_argnums=(1,),
            )
            return jitted.lower(
                params_abs, cache_abs, specs["tokens"], specs["index"]
            )
        raise ValueError(shape.kind)
