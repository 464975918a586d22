"""The hybrid decoder (Mamba-2 mixers beside attention without positions):
decode through the conv window and SSM state, the approximate operator in
the Mamba projections, the gated norm's own eps, and AxO entries padded to
the kernel's lanes at deploy time."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.axo import deploy_axo
from repro.axo.deploy import LANE
from repro.configs.base import ModelConfig, SSMConfig, StageConfig
from repro.launch.serve import demo_operator
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models.model import forward, logits_fn, model_spec
from repro.models.sharding import BASE_RULES
from repro.models.spec import init_params
from repro.models.ssm import mamba_decode, mamba_dims
from repro.obs import telemetry as tm

R = 0.22                   # a Granite residual multiplier, folded into norm_eps
BLOCK = (("mamba", "dense"), ("attn", "dense"), ("mamba", "dense"))


def _hybrid(chunk=4):
    cfg = ModelConfig(
        name="hybrid-test", family="hybrid", d_model=64, n_heads=4,
        kv_heads=2, d_ff=128, vocab=128,
        stages=(StageConfig(repeats=2, layers=BLOCK),),
        ssm=SSMConfig(d_state=16, head_dim=16, chunk=chunk, norm_eps=1e-5),
        pos_encoding="none", norm_eps=1e-5 / R**2, max_seq=64)
    return cfg, init_params(model_spec(cfg), seed=0, dtype=jnp.float32)


def _tokens(cfg, b=2, s=14):
    return jax.random.randint(jax.random.key(3), (b, s), 0, cfg.vocab)


def _serve(cfg, params, toks, n_prompt, **kw):
    """Logits (B, S - P + 1, V) of a prefill of P tokens, then a decode step
    for each later token."""
    pre = jax.jit(make_prefill_step(cfg, BASE_RULES, max_seq=toks.shape[1]))
    dec = jax.jit(make_decode_step(cfg, BASE_RULES))
    lg, cache = pre(params, toks[:, :n_prompt], **kw)
    out = [lg[:, 0]]
    for i in range(n_prompt, toks.shape[1]):
        lg, cache = dec(params, cache, toks[:, i:i + 1], jnp.int32(i), **kw)
        out.append(lg[:, 0])
    return jnp.stack(out, 1)


# a prompt inside one SSD chunk, and one over three chunks: the prefill's
# inter-chunk recurrence hands its state to decode
@pytest.mark.parametrize("n_prompt", [3, 10])
def test_prefill_then_decode_equals_the_full_forward(n_prompt):
    cfg, params = _hybrid()
    toks = _tokens(cfg)
    x, _, _ = forward(params, cfg, BASE_RULES, toks, mode="train")
    full = logits_fn(params, cfg, BASE_RULES, x)
    served = _serve(cfg, params, toks[:, :-1], n_prompt)
    np.testing.assert_allclose(np.asarray(served),
                               np.asarray(full[:, n_prompt - 1:-1]),
                               rtol=1e-4, atol=1e-4)


def test_ssm_group_deploys_every_mamba_projection():
    cfg, params = _hybrid()
    tel = tm.Telemetry("deploy")
    from repro.core.engine import ExecutionContext

    dep = deploy_axo(params, demo_operator(1), cfg, layers=("ssm",),
                     impl="xla", ctx=ExecutionContext(telemetry=tel))
    stage = dep.stages["0"]
    for li, (mixer, _) in enumerate(BLOCK):
        if mixer == "mamba":
            ent = stage[str(li)]["mixer"]
            assert set(ent) == {"in_proj", "out_proj"}
            d_in = mamba_dims(cfg)["d_in_proj"]
            assert ent["in_proj"]["bv"].shape == (2, cfg.d_model, d_in)
            assert ent["out_proj"]["gb"].shape == (2, 1, 2 * cfg.d_model, cfg.d_model)
        else:
            assert stage[str(li)] == {}
    assert dep.n_entries == 4
    (span,) = [s for s in tel.spans if s.name == "axo.deploy"]
    assert span.attrs["group_entries"] == {"ssm": 4}

    attn = deploy_axo(params, demo_operator(1), cfg, layers=("attn",), impl="xla")
    assert set(attn.stages["0"]) == {"0", "1", "2"}
    assert attn.stages["0"]["0"] == attn.stages["0"]["2"] == {}
    assert set(attn.stages["0"]["1"]["mixer"]) == {"wq", "wk", "wv", "wo"}


def test_axo_mamba_decode_equals_the_xla_path():
    """Prefill and decode with AxO in every Mamba projection: the Pallas
    kernel on entries padded to its lanes (in_proj's N is 296) gives the
    xla contraction's logits, and they are not the exact model's."""
    cfg, params = _hybrid()
    toks = _tokens(cfg, s=10)
    op = demo_operator(1)
    got = {impl: _serve(cfg, params, toks, 6, axo=deploy_axo(
        params, op, cfg, layers=("ssm",), impl=impl)) for impl in ("pallas", "xla")}
    np.testing.assert_allclose(np.asarray(got["pallas"]), np.asarray(got["xla"]),
                               rtol=1e-5, atol=1e-4)
    exact = _serve(cfg, params, toks, 6)
    assert float(jnp.max(jnp.abs(got["xla"] - exact))) > 1e-3


def test_gated_norm_keeps_its_published_eps_under_the_fold():
    """The residual fold divides the model's norm_eps by r^2; the gated norm
    reads no residual stream, so SSMConfig.norm_eps keeps it published."""
    cfg, params = _hybrid()
    p = jax.tree.map(lambda a: a[0], params["stages"]["0"]["0"]["mixer"])
    dims = mamba_dims(cfg)
    b = 2
    # small activations, so that the eps is a material part of the norm
    x = 1e-2 * jax.random.normal(jax.random.key(5), (b, 1, cfg.d_model))
    conv = jnp.zeros((b, cfg.ssm.d_conv - 1, dims["conv_dim"]))
    state = jnp.zeros((b, dims["n_heads"], cfg.ssm.head_dim, cfg.ssm.d_state))

    def out(c):
        return mamba_decode(p, x, c, BASE_RULES, conv, state)[0]

    published = dataclasses.replace(cfg, norm_eps=1e-5,
                                    ssm=dataclasses.replace(cfg.ssm, norm_eps=None))
    folded = dataclasses.replace(cfg, ssm=dataclasses.replace(cfg.ssm, norm_eps=None))
    np.testing.assert_array_equal(np.asarray(out(cfg)), np.asarray(out(published)))
    assert not np.allclose(np.asarray(out(cfg)), np.asarray(out(folded)), rtol=1e-3)


def _jaxpr_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _jaxpr_eqns(sub)


def test_padded_entry_is_padded_at_deploy_not_in_the_step():
    """in_proj at granite-4.0-h-micro's widths, (2048, 8512): the Pallas
    entry is stored padded to the kernel's lanes, so the jitted decode-shape
    apply pads no weight-sized operand, and it equals the unpadded xla
    path.  Entries already on the lanes are stored as they are."""
    cfg = ModelConfig(
        name="mamba-in-proj", family="ssm", d_model=2048, n_heads=32,
        kv_heads=8, d_ff=8192, vocab=128,
        stages=(StageConfig(repeats=1, layers=(("mamba", "none"),)),),
        ssm=SSMConfig())
    params = init_params(model_spec(cfg), seed=0, dtype=jnp.float32)
    op = demo_operator(1)
    deps = {impl: deploy_axo(params, op, cfg, layers=("ssm",), impl=impl)
            for impl in ("pallas", "xla")}
    ents = {impl: jax.tree.map(lambda a: a[0], d.stages["0"]["0"]["mixer"])
            for impl, d in deps.items()}
    assert ents["xla"]["in_proj"]["bv"].shape == (2048, 8512)
    assert ents["pallas"]["in_proj"]["bv"].shape == (2048, 8576)
    assert 8576 % LANE == 0
    for key in ("bv", "gb", "scale"):   # out_proj, (4096, 2048): as it was
        np.testing.assert_array_equal(np.asarray(ents["pallas"]["out_proj"][key]),
                                      np.asarray(ents["xla"]["out_proj"][key]))
    assert set(ents["pallas"]["out_proj"]) == {"bv", "gb", "scale"}

    x = jax.random.normal(jax.random.key(1), (8, 2048), jnp.float32)
    dep, ent = deps["pallas"], ents["pallas"]["in_proj"]
    jaxpr = jax.make_jaxpr(dep.apply)(x, ent).jaxpr
    pads = [e for e in _jaxpr_eqns(jaxpr) if e.primitive.name == "pad"]
    assert all(v.aval.size < 2048 * 128 for e in pads for v in e.invars), pads
    got = jax.jit(dep.apply)(x, ent)
    want = jax.jit(deps["xla"].apply)(x, ents["xla"]["in_proj"])
    assert got.shape == want.shape == (8, 8512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)
