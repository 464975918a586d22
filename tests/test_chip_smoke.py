"""CPU-side guarantees of the chip smoke script and the compile-cache switch."""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
from pathlib import Path
from unittest import mock

import jax
import numpy as np
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """No CPU fallback: the script stops before its first phase and prints
    no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=120, cwd=ROOT,
    )
    assert out.returncode != 0
    assert "phase" not in out.stdout
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path,
                                               config_updates):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert config_updates == []  # JAX's own reading of the variable stands


@pytest.mark.parametrize("tmpdir_name", ["a", "b"])
def test_compile_cache_defaults_to_a_fixed_repo_path(monkeypatch, tmp_path,
                                                     config_updates,
                                                     tmpdir_name):
    """The path is part of every cache key, so it may not follow the temp
    dir, the process or the clock."""
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    monkeypatch.setenv("TMPDIR", str(tmp_path / tmpdir_name))
    expected = str(ROOT / "experiments" / "cache" / "jax_compile")
    assert compile_cache.enable_compile_cache() == expected
    assert config_updates == [("jax_compilation_cache_dir", expected)]


@pytest.fixture
def smoke(monkeypatch):
    """``chip_smoke`` imported as a module, its app head cut to one K tile."""
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setattr(module, "APP_HEAD", (8, 128, 10))
    return module


def _head_configs():
    from repro.core.operator_model import accurate_config, spec_for

    spec = spec_for(8)
    rng = np.random.default_rng(0)
    cfgs = np.concatenate([accurate_config(spec)[None],
                           rng.integers(0, 2, (3, spec.n_luts))])
    return spec, cfgs.astype(np.uint8)


def test_app_head_parity_passes_on_the_pallas_kernel(smoke):
    """The smoke's app-head check, with the TPU default impl in interpret
    mode: exact against the oracle, one kernel dispatch."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.core.engine import ExecutionContext

    spec, cfgs = _head_configs()
    ctx = ExecutionContext(backend="jax", tuning="off")
    with mock.patch("repro.kernels.ops.on_tpu", return_value=True), \
            pltpu.force_tpu_interpret_mode():
        assert smoke.app_head_parity(spec, cfgs, 0, ctx) == 1


def test_app_head_parity_fails_off_the_pallas_kernel(smoke):
    """Off the TPU the default impl is the XLA GEMM, so the check refuses."""
    from repro.core.engine import ExecutionContext

    spec, cfgs = _head_configs()
    with pytest.raises(AssertionError, match="never ran"):
        smoke.app_head_parity(spec, cfgs, 0,
                              ExecutionContext(backend="jax", tuning="off"))
