"""Distributed semantics on simulated devices (subprocess keeps the main
pytest at 1 device -- the dry-run flag must never leak into other tests)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
def test_moe_ep_shard_map_matches_reference():
    """Expert-parallel shard_map MoE == single-device reference dispatch."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        AUTO = AxisType.Auto
        from repro.configs.registry import get_arch
        from repro.models.moe import moe_spec, moe_apply
        from repro.models.sharding import BASE_RULES
        from repro.models.spec import init_params

        cfg = get_arch("jamba-v0.1-52b").reduced()   # 8 experts top-2
        p = init_params(moe_spec(cfg), seed=0, dtype=jnp.float32)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 16, cfg.d_model)), jnp.float32)

        ref, aux_ref = moe_apply(p, x, cfg, BASE_RULES)  # no mesh -> reference

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AUTO, AUTO))
        with jax.set_mesh(mesh):
            ep, aux_ep = jax.jit(lambda p, x: moe_apply(p, x, cfg, BASE_RULES))(p, x)

        err = float(jnp.max(jnp.abs(ref - ep)))
        print("ERR", err, float(aux_ref), float(aux_ep))
        assert err < 2e-4, err
        assert abs(float(aux_ref) - float(aux_ep)) < 1e-5
    """)
    assert "ERR" in out


@pytest.mark.slow
def test_mini_dryrun_lowers_and_compiles():
    """A reduced arch lowers + compiles on a (2, 4) mesh with the real
    dry-run plumbing (shardings, donation, cost/memory analysis)."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        AUTO = AxisType.Auto
        from repro.configs.base import ShapeConfig
        from repro.configs.registry import get_arch, rules_for
        from repro.launch.lowering import lower_step
        from repro.models.sharding import BASE_RULES

        cfg = get_arch("internlm2-1.8b").reduced()
        shape = ShapeConfig("mini_train", 64, 8, "train")
        rules = rules_for(cfg, shape, mesh_model=4, mesh_data=2)
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AUTO, AUTO))
        lowered = lower_step(cfg, shape, mesh, rules)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        print("FLOPS", float(cost.get("flops", 0)))
        assert float(cost.get("flops", 0)) > 0
        print("MEM", compiled.memory_analysis().temp_size_in_bytes)
    """)
    assert "FLOPS" in out and "MEM" in out


@pytest.mark.slow
def test_train_step_numerically_equal_on_mesh_vs_single():
    """SPMD execution on 8 simulated devices == single-device math."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        AUTO = AxisType.Auto
        from repro.configs.base import ShapeConfig
        from repro.configs.registry import get_arch
        from repro.data.synthetic import SyntheticLM
        from repro.launch.steps import make_train_step
        from repro.models.model import model_spec
        from repro.models.sharding import BASE_RULES, named_sharding
        from repro.models.spec import init_params, param_shardings
        from repro.optim import make_optimizer, cosine_schedule
        from jax.sharding import PartitionSpec as P

        cfg = get_arch("granite-3-2b").reduced()
        params = init_params(model_spec(cfg), seed=0, dtype=jnp.float32)
        data = SyntheticLM(cfg, ShapeConfig("t", 32, 8, "train"))
        batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        opt = make_optimizer("adamw", cosine_schedule(1e-3))
        fn = make_train_step(cfg, BASE_RULES, opt)

        p1, o1, m1 = jax.jit(fn)(params, opt.init(params), jnp.int32(0), batch)
        loss_single = float(m1["loss"])

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AUTO, AUTO))
        with jax.set_mesh(mesh):
            spec = model_spec(cfg)
            p_sh = param_shardings(spec, BASE_RULES, mesh)
            params_m = jax.device_put(params, p_sh)
            o_sh = param_shardings(opt.state_spec(spec), BASE_RULES, mesh)
            opt_m = jax.device_put(opt.init(params), o_sh)
            batch_m = jax.device_put(
                batch, jax.tree.map(
                    lambda x: named_sharding(mesh, P("data"), x.shape), batch))
            p2, o2, m2 = jax.jit(fn, in_shardings=(p_sh, o_sh, None, None))(
                params_m, opt_m, jnp.int32(0), batch_m)
        loss_mesh = float(m2["loss"])
        print("LOSS", loss_single, loss_mesh)
        assert abs(loss_single - loss_mesh) < 5e-3 * max(1.0, abs(loss_single))
    """)
    assert "LOSS" in out
