"""AxO deployment: rank-R factorization quality, axo_linear semantics, and
the code lookup's select path against the gathers it replaces."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.axo import AxOOperator, axo_linear, quantize_tensor
from repro.axo.deploy import AxODeployment, code_lookup, lookup_path
from repro.core.operator_model import (
    accurate_config,
    error_tables,
    product_tables,
    spec_for,
)

RNG = np.random.default_rng(0)


def _random_config(seed=0):
    spec = spec_for(8)
    return np.random.default_rng(seed).integers(0, 2, spec.n_luts).astype(np.uint8)


def test_accurate_operator_has_zero_error_tables():
    spec = spec_for(8)
    op = AxOOperator.from_config(accurate_config(spec), rank=4)
    b = op.rank_behav()
    assert b["MAX_ABS_ERR"] < 1e-6
    # axo_linear == plain quantized matmul for the accurate operator
    x = jnp.asarray(RNG.standard_normal((8, 16)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((16, 4)), jnp.float32)
    y = axo_linear(x, w, op)
    xq, sx = quantize_tensor(x)
    wq, sw = quantize_tensor(w)
    half = 128
    xs = jnp.where(xq >= half, xq - 256, xq).astype(jnp.float32)
    ws = jnp.where(wq >= half, wq - 256, wq).astype(jnp.float32)
    ref = (xs @ ws) * (sx * sw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_rank_behav_improves_with_rank():
    cfg = _random_config(1)
    errs = [AxOOperator.from_config(cfg, rank=r).rank_behav()["AVG_ABS_ERR"]
            for r in (1, 4, 16, 64)]
    # non-increasing (ties possible once R exceeds the error table's true rank)
    for lo, hi in zip(errs[1:], errs[:-1]):
        assert lo <= hi * (1 + 1e-9)
    assert errs[-1] < 0.05 * (errs[0] + 1e-9)


def test_axo_linear_converges_to_true_operator_semantics():
    """With growing rank, axo_linear approaches the bit-exact table matmul."""
    from repro.kernels.ref import ref_axo_matmul_exact

    cfg = _random_config(2)
    x = jnp.asarray(RNG.standard_normal((16, 32)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((32, 8)), jnp.float32)
    rel = []
    for r in (1, 8, 32):
        op = AxOOperator.from_config(cfg, rank=r)
        xq, sx = quantize_tensor(x)
        wq, sw = quantize_tensor(w)
        true = ref_axo_matmul_exact(xq, wq, jnp.asarray(op.table)).astype(
            jnp.float32) * (sx * sw)
        y = axo_linear(x, w, op)
        rel.append(float(jnp.linalg.norm(y - true) / jnp.linalg.norm(true)))
    assert rel == sorted(rel, reverse=True)
    assert rel[-1] < 0.02


def test_axo_linear_uses_kernel_on_aligned_shapes():
    cfg = _random_config(3)
    op = AxOOperator.from_config(cfg, rank=4)
    x = jnp.asarray(RNG.standard_normal((128, 128)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((128, 128)), jnp.float32)
    y_kernel = axo_linear(x, w, op, use_kernel=True)
    y_ref = axo_linear(x, w, op, use_kernel=False)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_axo_linear_batched_shape():
    op = AxOOperator.from_config(_random_config(4), rank=2)
    x = jnp.asarray(RNG.standard_normal((2, 5, 16)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((16, 6)), jnp.float32)
    assert axo_linear(x, w, op).shape == (2, 5, 6)


# ---------------------------------------------------------------------------
# code_lookup: select over the table where it can, bit-identical to a gather
# ---------------------------------------------------------------------------


def _operator(n_bits=8, rank=1, signed=True, seed=0):
    """Rank-R factors of a random config's error table, for either
    operand interpretation (``from_config`` builds signed operators only)."""
    spec = spec_for(n_bits, signed=signed)
    config = np.random.default_rng(seed).integers(0, 2, spec.n_luts)
    config = config.astype(np.uint8)[None]
    u, s, vt = np.linalg.svd(error_tables(spec, config)[0].astype(np.float64))
    return AxOOperator(
        n_bits=n_bits, rank=rank,
        f_table=(u[:, :rank] * s[:rank]).astype(np.float32),
        g_table=vt[:rank].T.astype(np.float32),
        signed_vals=spec.operand_values.astype(np.int32),
        table=product_tables(spec, config)[0],
    )


def _wide_operator(n_bits=12, rank=1):
    """A 12-bit operator with random factors: too wide to select over."""
    rng = np.random.default_rng(n_bits)
    n = 1 << n_bits
    codes = np.arange(n)
    return AxOOperator(
        n_bits=n_bits, rank=rank,
        f_table=rng.standard_normal((n, rank)).astype(np.float32),
        g_table=rng.standard_normal((n, rank)).astype(np.float32),
        signed_vals=np.where(codes >= n // 2, codes - n, codes).astype(np.int32),
        table=np.zeros((1, 1), np.int32),
    )


def _sign_magnitude(op):
    """``op`` with a decoding that is neither two's complement nor the code."""
    codes = np.arange(1 << op.n_bits)
    half = 1 << (op.n_bits - 1)
    sv = np.where(codes >= half, -(codes - half), codes).astype(np.int32)
    return dataclasses.replace(op, signed_vals=sv)


LOOKUP_CASES = {
    "signed8-r1": lambda: _operator(8, 1),
    "signed8-r4": lambda: _operator(8, 4),
    "unsigned8-r1": lambda: _operator(8, 1, signed=False),
    "unsigned8-r4": lambda: _operator(8, 4, signed=False),
    "signed4-r1": lambda: _operator(4, 1),
    "signed4-r4": lambda: _operator(4, 4),
    "wide12-r1": lambda: _wide_operator(12, 1),
    "sign-magnitude8-r4": lambda: _sign_magnitude(_operator(8, 4)),
}
SELECT_CASES = [c for c in LOOKUP_CASES if not c.startswith(("wide", "sign-"))]


def _bits(a) -> np.ndarray:
    return np.asarray(a, np.float32).view(np.uint32)


def _gather_form(op, codes, table):
    """The lookups as the deployment first wrote them: two gathers."""
    sv = jnp.asarray(op.signed_vals, jnp.float32)
    return sv[codes], jnp.moveaxis(jnp.asarray(table, jnp.float32)[codes], -1, 0)


@pytest.mark.parametrize("case", sorted(LOOKUP_CASES))
def test_code_lookup_is_bit_exact_on_every_code(case):
    op = LOOKUP_CASES[case]()
    assert lookup_path(op) == ("select" if case in SELECT_CASES else "gather")
    n = 1 << op.n_bits
    codes = jnp.asarray(np.random.default_rng(n).permutation(n).reshape(-1, 8),
                        jnp.int32)
    lookup = jax.jit(code_lookup, static_argnums=(0, 2))
    for side, table in (("f", op.f_table), ("g", op.g_table)):
        vals, factors = lookup(op, codes, side)
        want_vals, want_factors = _gather_form(op, codes, table)
        assert vals.dtype == jnp.float32 and factors.shape == (op.rank, n // 8, 8)
        assert np.array_equal(_bits(vals), _bits(want_vals)), (case, side)
        assert np.array_equal(_bits(factors), _bits(want_factors)), (case, side)


def _weight_entry(op, k, n, seed=0):
    w = jnp.asarray(np.random.default_rng(seed).standard_normal((k, n)),
                    jnp.float32)
    wq, sw = quantize_tensor(w, op.n_bits)
    bv, gb = _gather_form(op, wq, op.g_table)
    return {"bv": bv, "gb": gb, "scale": sw}


@pytest.mark.parametrize("case", sorted(LOOKUP_CASES))
def test_apply_matches_the_gather_form_bit_for_bit(case):
    op = LOOKUP_CASES[case]()
    k, n = 64, 48
    entry = _weight_entry(op, k, n)
    x = jnp.asarray(RNG.standard_normal((2, 3, k)), jnp.float32)

    def gather_apply(x, entry):
        xq, sx = quantize_tensor(x.reshape(-1, k), op.n_bits)
        av, fa = _gather_form(op, xq, op.f_table)
        y = av @ entry["bv"] + jnp.einsum("rmk,rkn->mn", fa, entry["gb"])
        return (y * (sx * entry["scale"])).reshape(2, 3, n)

    dep = AxODeployment(op=op, impl="xla", layers=("attn",))
    got = jax.jit(dep.apply)(x, entry)
    want = jax.jit(gather_apply)(x, entry)
    assert got.shape == (2, 3, n)
    assert np.array_equal(_bits(got), _bits(want)), case


def test_deployed_entries_equal_the_gather_form():
    """deploy_axo's cached values and right factors, stacked and not, are
    the gathers of the weight codes, bit for bit."""
    from repro.axo import deploy_axo
    from repro.configs.registry import get_arch
    from repro.models.model import model_spec
    from repro.models.spec import init_params

    cfg = get_arch("granite-3-2b").reduced()
    params = init_params(model_spec(cfg), seed=0, dtype=jnp.float32)
    op = _operator(8, 4)
    dep = deploy_axo(params, op, cfg, layers=("attn", "head"), impl="xla")
    wq_stack = params["stages"]["0"]["0"]["mixer"]["wq"]
    w = wq_stack.reshape(wq_stack.shape[0], wq_stack.shape[1], -1)
    for got, w2d in ((dep.stages["0"]["0"]["mixer"]["wq"], w[0]),
                     (dep.head, params["embed"]["tok"].T)):
        codes, _ = quantize_tensor(w2d, op.n_bits)
        bv, gb = _gather_form(op, codes, op.g_table)
        stacked = got["bv"].ndim == 3
        assert np.array_equal(_bits(got["bv"][0] if stacked else got["bv"]),
                              _bits(bv))
        assert np.array_equal(_bits(got["gb"][0] if stacked else got["gb"]),
                              _bits(gb))


def _decode_apply_hlo(op, m=8, k=2048, n=2048) -> str:
    """HLO of a jitted apply at granite's decode shape: 8 tokens, 2048 in,
    2048 out."""
    dep = AxODeployment(op=op, impl="xla", layers=("attn",))
    f32 = jnp.float32
    entry = {"bv": jax.ShapeDtypeStruct((k, n), f32),
             "gb": jax.ShapeDtypeStruct((op.rank, k, n), f32),
             "scale": jax.ShapeDtypeStruct((), f32)}
    lowered = jax.jit(dep.apply).lower(jax.ShapeDtypeStruct((m, k), f32), entry)
    return lowered.as_text(dialect="hlo")


@pytest.mark.parametrize("case", ["signed8-r1", "wide12-r1",
                                  "sign-magnitude8-r4"])
def test_decode_apply_gathers_only_on_the_fallback(case):
    op = LOOKUP_CASES[case]()
    text = _decode_apply_hlo(op)
    has_gather = re.search(r" gather\(", text) is not None
    assert has_gather == (case not in SELECT_CASES), case
