"""End-to-end AxO serving: registry-backed kernel dispatch at decode shapes,
whole-model deployment entry structure, and generation fidelity of a
fully-deployed reduced LM vs the exact serving path."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.axo import AXO_LAYERS, AxOOperator, axo_linear, deploy_axo
from repro.axo import deploy as deploy_mod
from repro.configs.base import ShapeConfig
from repro.configs.registry import get_arch
from repro.core.operator_model import accurate_config, spec_for
from repro.data.synthetic import SyntheticLM
from repro.kernels import ops, registry
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models.model import forward, logits_fn, model_spec
from repro.models.sharding import BASE_RULES
from repro.models.spec import init_params

RNG = np.random.default_rng(0)


def _mild_op(rank=16):
    """1-column truncation of the first CC row: a mild Pareto design."""
    spec8 = spec_for(8)
    cfg = accurate_config(spec8)
    cfg[0] = 0
    return AxOOperator.from_config(cfg, rank=rank)


def _granite():
    cfg = get_arch("granite-3-2b").reduced()
    params = init_params(model_spec(cfg), seed=0, dtype=jnp.float32)
    return cfg, params


# ---------------------------------------------------------------------------
# Registry integration
# ---------------------------------------------------------------------------


def test_serving_kernels_are_registered():
    assert "pallas" in registry.impl_names("axo_matmul")
    assert "pallas" in registry.impl_names("flash_attention")
    axo = registry.get("axo_matmul.pallas")
    assert set(dict(axo.tunables)) == {"bm", "bn", "bk"}
    fa = registry.get("flash_attention.pallas")
    assert set(dict(fa.tunables)) == {"bq", "bk"}
    # both expose cost/VMEM formulas for the autotuner
    cost = axo.cost_estimate(m=128, k=128, n=128, rank=4, bm=128, bn=128, bk=128)
    assert cost["flops"] > 0 and cost["bytes_accessed"] > 0


def test_axo_linear_decode_shape_dispatches_pallas(monkeypatch):
    """M=4, K=N=128 (a decode microbatch) must hit the Pallas kernel -- the
    historical ``% 128`` gate demoted it to the reference path."""
    calls = []
    real = ops.axo_matmul

    def spy(*a, **kw):
        calls.append((a[0].shape, a[1].shape))
        return real(*a, **kw)

    monkeypatch.setattr(ops, "axo_matmul", spy)
    op = _mild_op(rank=2)
    x = jnp.asarray(RNG.standard_normal((4, 128)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((128, 128)), jnp.float32)
    y = axo_linear(x, w, op, use_kernel=True)
    assert calls == [((4, 128), (128, 128))]
    ref = axo_linear(x, w, op, use_kernel=False)
    assert calls == [((4, 128), (128, 128))]   # ref path stays off-kernel
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_deployment_decode_shape_dispatches_pallas(monkeypatch):
    calls = []
    real = deploy_mod.axo_matmul_pallas

    def spy(*a, **kw):
        calls.append(a[0].shape)
        return real(*a, **kw)

    monkeypatch.setattr(deploy_mod, "axo_matmul_pallas", spy)
    cfg, params = _granite()
    dep = deploy_axo(params, _mild_op(rank=2), cfg,
                     layers=("head",), impl="pallas")
    x = jnp.asarray(RNG.standard_normal((4, cfg.d_model)), jnp.float32)
    out = dep.apply(x, dep.head)
    # the entry is stored padded to the kernel's lanes (d_model 64 -> K 128),
    # and the activation meets it there; the output keeps the vocabulary
    assert calls == [(4, 128)] and dep.head["bv"].shape[0] == 128
    assert out.shape == (4, cfg.vocab)


def _fallback_op(rank=2):
    """The mild design with a sign-magnitude decoding: neither two's
    complement nor the code itself, so its lookups stay gathers."""
    op = _mild_op(rank)
    codes = np.arange(1 << op.n_bits)
    sv = np.where(codes >= 128, 128 - codes, codes).astype(np.int32)
    return dataclasses.replace(op, signed_vals=sv)


@pytest.mark.parametrize("path", ["select", "gather"])
def test_apply_counts_its_lookup_path(path):
    """Each trace of ``apply`` counts the lookup it engaged, beside the
    kernel dispatch count."""
    from repro.core.engine import ExecutionContext
    from repro.obs import telemetry as tm

    cfg, params = _granite()
    op = _mild_op(rank=2) if path == "select" else _fallback_op(rank=2)
    tel = tm.Telemetry("lookup")
    dep = deploy_axo(params, op, cfg, layers=("head",), impl="xla",
                     ctx=ExecutionContext(telemetry=tel))
    assert deploy_mod.lookup_path(op) == path
    x = jnp.asarray(RNG.standard_normal((4, cfg.d_model)), jnp.float32)
    dep.apply(x, dep.head)
    other = {"select": "gather", "gather": "select"}[path]
    assert tel.counter(f"dispatch.axo_lookup.{path}") == 1
    assert tel.counter(f"dispatch.axo_lookup.{other}") == 0
    assert tel.counter("dispatch.axo_apply.xla") == 1


# ---------------------------------------------------------------------------
# Deployment structure + per-entry semantics
# ---------------------------------------------------------------------------


def test_deploy_entry_counts_and_validation():
    cfg, params = _granite()
    op = _mild_op(rank=4)
    # granite reduced: 1 attn/dense block -> wq wk wv wo + gate/up/down + head
    assert deploy_axo(params, op, cfg).n_entries == 8
    assert deploy_axo(params, op, cfg, layers=("head",)).n_entries == 1
    assert deploy_axo(params, op, cfg, layers=("attn",)).n_entries == 4
    with pytest.raises(ValueError, match="unknown AxO layer"):
        deploy_axo(params, op, cfg, layers=("attn", "lstm"))
    with pytest.raises(ValueError, match="impl"):
        deploy_axo(params, op, cfg, impl="cuda")


def test_deploy_is_one_span_with_its_entries_and_bytes():
    from repro.core.engine import ExecutionContext
    from repro.obs import telemetry as tm

    cfg, params = _granite()
    tel = tm.Telemetry("deploy")
    dep = deploy_axo(params, _mild_op(rank=2), cfg, layers=("attn",),
                     impl="xla", ctx=ExecutionContext(telemetry=tel))
    (span,) = [s for s in tel.spans if s.name == "axo.deploy"]
    assert span.duration_s > 0
    assert span.attrs["entries"] == dep.n_entries == 4
    assert span.attrs["bytes"] == sum(x.nbytes for x in jax.tree.leaves(dep))
    assert span.attrs["impl"] == "xla" and span.attrs["layers"] == ("attn",)


def test_deployment_entries_cache_weight_factors():
    """Entries carry pre-gathered signed values and G_r(W) with the stacked
    repeats axis; head is unstacked (d, vocab)."""
    cfg, params = _granite()
    op = _mild_op(rank=3)
    dep = deploy_axo(params, op, cfg, impl="xla")
    rep = cfg.stages[0].repeats
    d = cfg.d_model
    ent = dep.stages["0"]["0"]["mixer"]["wq"]
    assert ent["bv"].shape[:2] == (rep, d)
    assert ent["gb"].shape[:3] == (rep, 3, d)
    assert ent["scale"].shape == (rep,)
    assert dep.head["bv"].shape[0] == d
    assert dep.head["gb"].shape[0] == 3


def test_deployment_enters_steps_as_an_argument():
    """The deployment is a pytree argument of the jitted steps: none of its
    entries is captured as a program constant (at published widths they are
    gigabytes, which the compiler would copy on the host)."""
    cfg, params = _granite()
    dep = deploy_axo(params, _mild_op(rank=2), cfg, impl="xla")
    n_dep = len(jax.tree.leaves(dep))
    assert n_dep == 3 * dep.n_entries   # (bv, gb, scale) each; tables static
    toks = jnp.zeros((2, 8), jnp.int32)
    step = make_prefill_step(cfg, BASE_RULES, max_seq=8)
    closed = jax.make_jaxpr(step)(params, toks, axo=dep)
    assert len(closed.in_avals) == len(jax.tree.leaves(params)) + 1 + n_dep
    const_bytes = sum(np.asarray(c).nbytes for c in closed.consts)
    assert const_bytes < dep.stages["0"]["0"]["mixer"]["wq"]["bv"].nbytes
    # rebuilt from its leaves, it keeps the static operator and layout
    leaves, tree = jax.tree.flatten(dep)
    again = jax.tree.unflatten(tree, leaves)
    assert again.op is dep.op and again.n_entries == dep.n_entries


def test_head_apply_matches_axo_linear():
    """dep.apply on the cached head entry == axo_linear on the raw weight."""
    cfg, params = _granite()
    op = _mild_op(rank=8)
    dep = deploy_axo(params, op, cfg, layers=("head",), impl="xla")
    w = (params["embed"]["tok"].T if cfg.tie_embeddings
         else params["embed"]["unembed"]).astype(jnp.float32)
    x = jnp.asarray(RNG.standard_normal((6, cfg.d_model)), jnp.float32)
    got = dep.apply(x, dep.head)
    want = axo_linear(x, w, op, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_deployment_pallas_matches_xla_contraction():
    cfg, params = _granite()
    op = _mild_op(rank=4)
    dep_p = deploy_axo(params, op, cfg, layers=("head",), impl="pallas")
    dep_x = deploy_axo(params, op, cfg, layers=("head",), impl="xla")
    x = jnp.asarray(RNG.standard_normal((4, cfg.d_model)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(dep_p.apply(x, dep_p.head)),
        np.asarray(dep_x.apply(x, dep_x.head)),
        rtol=1e-5, atol=1e-4,
    )


def test_deep_arch_deploys_mla_and_moe():
    """deepseek reduced exercises the MLA + MoE expert walk."""
    cfg = get_arch("deepseek-v3-671b").reduced()
    params = init_params(model_spec(cfg), seed=0, dtype=jnp.float32)
    dep = deploy_axo(params, _mild_op(rank=2), cfg, impl="xla")
    assert dep.n_entries == 18
    li = next(iter(dep.stages["0"]))
    mixer = dep.stages["0"][li]["mixer"]
    assert set(mixer) == {"wq_a", "wq_b", "wkv_a", "wo"}   # wkv_b stays exact


# ---------------------------------------------------------------------------
# End-to-end: fully-deployed reduced model serving fidelity
# ---------------------------------------------------------------------------


def _generate(prefill, decode, params, toks, gen):
    plen = toks.shape[1]
    logits, cache = prefill(params, toks)
    nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out, lgs = [nxt], [logits[:, -1]]
    for i in range(plen, plen + gen - 1):
        logits, cache = decode(params, cache, nxt, jnp.int32(i))
        nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out.append(nxt)
        lgs.append(logits[:, -1])
    return jnp.concatenate(out, 1), lgs


def _replay(prefill, decode, params, toks, trajectory):
    plen = toks.shape[1]
    logits, cache = prefill(params, toks)
    lgs = [logits[:, -1]]
    for j in range(trajectory.shape[1] - 1):
        logits, cache = decode(params, cache, trajectory[:, j:j + 1],
                               jnp.int32(plen + j))
        lgs.append(logits[:, -1])
    return lgs


def test_fully_deployed_generation_tracks_exact():
    """Rank-16 mild-design deployment in EVERY linear layer: teacher-forced
    greedy decisions along the exact trajectory stay within the top-1
    agreement bound (int8 quantization + mild operator error)."""
    cfg, params = _granite()
    rules = BASE_RULES
    batch, plen, gen = 2, 8, 6
    max_seq = plen + gen
    data = SyntheticLM(cfg, ShapeConfig("serve", max_seq, batch, "train"), seed=0)
    toks = jnp.asarray(data.batch(0)["tokens"])[:, :plen]

    prefill = jax.jit(make_prefill_step(cfg, rules, max_seq=max_seq))
    decode = jax.jit(make_decode_step(cfg, rules))
    exact_toks, exact_lgs = _generate(prefill, decode, params, toks, gen)

    dep = deploy_axo(params, _mild_op(rank=16), cfg,
                     layers=AXO_LAYERS, impl="xla")
    assert dep.n_entries == 8
    pre_a = functools.partial(prefill, axo=dep)
    dec_a = functools.partial(decode, axo=dep)
    rep = _replay(pre_a, dec_a, params, toks, exact_toks)
    top1 = float(np.mean([
        (jnp.argmax(a, -1) == jnp.argmax(e, -1)).mean()
        for a, e in zip(rep, exact_lgs)]))
    rel = float(np.mean([
        jnp.linalg.norm(a - e) / jnp.maximum(jnp.linalg.norm(e), 1e-9)
        for a, e in zip(rep, exact_lgs)]))
    assert top1 >= 0.5, (top1, rel)
    assert rel < 0.5, (top1, rel)


def test_head_only_deployment_changes_only_logits():
    """Head-only deployment leaves hidden states bit-identical; logits differ
    only by the quantized head matmul."""
    cfg, params = _granite()
    toks = jnp.asarray(
        SyntheticLM(cfg, ShapeConfig("smoke", 16, 2, "train")).batch(0)["tokens"])
    dep = deploy_axo(params, _mild_op(rank=16), cfg,
                     layers=("head",), impl="xla")
    x_ref, _, _ = forward(params, cfg, BASE_RULES, toks, mode="train")
    x_axo, _, _ = forward(params, cfg, BASE_RULES, toks, mode="train", axo=dep)
    np.testing.assert_array_equal(np.asarray(x_ref), np.asarray(x_axo))
    lg_ref = logits_fn(params, cfg, BASE_RULES, x_ref)
    lg_axo = logits_fn(params, cfg, BASE_RULES, x_axo, axo=dep)
    rel = float(jnp.linalg.norm(lg_axo - lg_ref) / jnp.linalg.norm(lg_ref))
    assert 0 < rel < 0.1
