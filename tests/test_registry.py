"""Kernel registry: per-engine menus, constrained tile spaces, and the
acceptance property -- every registered impl is bit-identical to its oracle
under interpret mode for ALL admissible tile candidates (the full tunable
space at a small bucket, exhaustively enumerated)."""

import numpy as np
import pytest

from repro.core.engine import ExecutionContext
from repro.kernels import registry, tuning

# Small buckets keep the exhaustive candidate sweep fast (4-bit operator,
# tiny populations) while still spanning multi-tile grids in every axis.
SMALL_BUCKETS = {
    "fastchar": dict(n_bits=4, d=8),
    "fastapp": dict(n_bits=4, d=8, m=8, k=24, n=8),
    "fastmoo": dict(p=48, n_obj=2),
    "axo_matmul": dict(m=24, k=160, n=136, rank=3),       # awkward on purpose
    "flash_attention": dict(sq=40, skv=40, hd=16),
}


# ---------------------------------------------------------------------------
# Registry contents + menus
# ---------------------------------------------------------------------------


def test_every_engine_has_registered_impls():
    assert registry.impl_names("fastchar") == (
        "xla", "pallas", "entry", "entry_pallas"
    )
    assert registry.impl_names("fastapp") == (
        "gemm", "xla", "pallas", "entry", "entry_pallas"
    )
    assert registry.impl_names("fastmoo") == ("xla", "pallas")
    assert registry.impl_names("axo_matmul") == ("xla", "pallas")
    assert registry.impl_names("flash_attention") == ("xla", "pallas")
    with pytest.raises(ValueError):
        registry.impl_names("fastray")


def test_get_unknown_kernel_raises():
    with pytest.raises(KeyError, match="no kernel"):
        registry.get("fastchar.cuda")


def test_duplicate_registration_rejected():
    spec = registry.get("fastchar.pallas")
    with pytest.raises(ValueError, match="already registered"):
        registry.register(spec)


def test_describe_lists_every_spec():
    text = registry.describe()
    for s in registry.registered():
        assert s.name in text


def test_resolve_impl_engine_names_and_legacy_tuples():
    ctx = ExecutionContext(backend="jax", kernel_impl="gemm")
    # engine names read the registry menus
    assert ctx.resolve_impl("fastapp") == "gemm"
    assert ctx.resolve_impl("fastchar") is None
    assert ctx.resolve_impl("fastmoo", "xla") == "xla"
    # legacy tuple form keeps working
    assert ctx.resolve_impl(("gemm", "xla")) == "gemm"
    assert ctx.resolve_impl(("xla", "pallas"), "xla") == "xla"


def test_tuning_policy_validated_eagerly():
    assert ExecutionContext(tuning="cached").tuning == "cached"
    with pytest.raises(ValueError, match="tuning"):
        ExecutionContext(tuning="always")


# ---------------------------------------------------------------------------
# Tile spaces
# ---------------------------------------------------------------------------


def test_char_candidates_respect_int32_bound():
    spec = registry.get("fastchar.pallas")
    bucket = spec.bucket(n_bits=8, d=256)
    cands = spec.candidates(bucket)
    assert cands, "8-bit bucket must admit candidates"
    for tiles in cands:
        a_tile = tiles["a_tile"]
        assert 256 % a_tile == 0
        assert a_tile * 256 * 59904 < (1 << 31)  # max_abs_error_bound(8x8)
    # the full 256-wide A tile overflows int32 partials and must be excluded
    assert not any(t["a_tile"] == 256 for t in cands)


def test_default_tiles_are_admissible_everywhere():
    for spec in registry.registered():
        if not spec.tunables:
            continue
        engine_shape = SMALL_BUCKETS[spec.engine]
        for shape in (engine_shape,):
            bucket = spec.bucket(**shape)
            tiles = spec.default_tiles(bucket)
            assert spec.constraint is None or spec.constraint(bucket, tiles), (
                spec.name, bucket, tiles
            )


def test_cost_and_compiler_params_are_plain_dicts():
    spec = registry.get("fastchar.pallas")
    cost = spec.cost_estimate(rows=2, d=8, a=16, b=16, a_tile=8)
    assert set(cost) == {"flops", "bytes_accessed", "transcendentals"}
    params = spec.params_fn(rows=2, d_block=4, a_tile=8, b=16)
    assert params["dimension_semantics"] == ("parallel", "parallel")
    assert params["vmem_limit_bytes"] >= (4 << 20)
    # the spec wraps the dict into the one CompilerParams the kernels pass
    built = spec.compiler_params(rows=2, d_block=4, a_tile=8, b=16)
    assert tuple(built.dimension_semantics) == ("parallel", "parallel")
    assert built.vmem_limit_bytes == params["vmem_limit_bytes"]
    gemv = registry.get("fastapp.pallas")
    assert tuple(gemv.compiler_params(m=8, k_tile=128, n=8, a=16, rows=2)
                 .dimension_semantics) == ("parallel", "arbitrary")


# ---------------------------------------------------------------------------
# Acceptance property: oracle parity over the whole tile space
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", [s.name for s in registry.registered()])
def test_every_tile_candidate_matches_oracle(name):
    """Exhaustive property over the admissible tile space: each candidate's
    integer outputs are bit-identical to the oracle (f32 channels ~1e-6)."""
    spec = registry.get(name)
    bucket = spec.bucket(**SMALL_BUCKETS[spec.engine])
    oracle = tuning.oracle_case(spec, bucket)
    cands = spec.candidates(bucket) or [spec.default_tiles(bucket)]
    assert len(cands) >= 1
    for tiles in cands:
        exact_r, close_r = tuning.run_case(spec, bucket, tiles)
        for r, o in zip(exact_r, oracle[0]):
            np.testing.assert_array_equal(
                np.asarray(r), np.asarray(o),
                err_msg=f"{name} tiles={tiles}",
            )
        for r, o in zip(close_r, oracle[1]):
            scale = float(np.max(np.abs(np.asarray(o)))) + 1.0
            np.testing.assert_allclose(
                np.asarray(r), np.asarray(o),
                rtol=spec.tol, atol=spec.tol * scale,
                err_msg=f"{name} tiles={tiles}",
            )


def test_entry_gemv_admits_12bit_where_table_kernel_cannot():
    """The table-free GEMV synthesizes its per-row planes, so its constraint
    admits 12-bit operands; the table kernel's planes come from the RowTables
    constant (1 GiB at that width), which excludes every candidate."""
    shape = dict(n_bits=12, d=4, m=8, k=128, n=8)
    table = registry.get("fastapp.pallas")
    entry = registry.get("fastapp.entry_pallas")
    assert not table.candidates(table.bucket(**shape))
    assert entry.candidates(entry.bucket(**shape))


def test_moo_2d_friendly_default_layout():
    """The dominance kernel's registered default is the (tile, 128) layout on
    big-population buckets (j = lane axis), shrinking with the bucket."""
    spec = registry.get("fastmoo.pallas")
    assert spec.default_tiles(spec.bucket(p=512, n_obj=2)) == {
        "tile": 64, "j_tile": 128,
    }
    assert spec.default_tiles(spec.bucket(p=16, n_obj=2)) == {
        "tile": 16, "j_tile": 16,
    }


def test_vmem_limits_count_lane_and_sublane_padding():
    """A (P, 1) column occupies whole 128-lane tiles and a (1, P) row whole
    8-sublane tiles; the dominance kernel's limit must cover them."""
    assert registry._vmem_bytes(256, 1) == 4 * 256 * 128
    assert registry._vmem_bytes(1, 256) == 4 * 8 * 256
    assert registry._vmem_bytes(3, 5, 130) == 4 * 3 * 8 * 256
    spec = registry.get("fastmoo.pallas")
    limit = spec.params_fn(tile=64, j_tile=128, n_obj=2)["vmem_limit_bytes"]
    padded_cols = 3 * registry._vmem_bytes(64, 1)
    assert limit >= 2 * padded_cols + 10 * registry._vmem_bytes(64, 128)
