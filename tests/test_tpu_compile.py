"""Compile the main-path Pallas kernels for a described TPU v5e (no chip).

Interpret mode, which every other kernel test runs, cannot see what the
chip's compiler refuses: unaligned blocks, scoped-VMEM overflows, layouts
Mosaic cannot lower.  Each case lowers one kernel at the shape its main path
runs and compiles it for one chip of a described ``v5e:2x2`` topology; a
refusal fails the test.  The topology is described inside a fixture, never
at import time: only one process may load the TPU compiler's library, and
test collection must not depend on which one does.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.operator_model import spec_for


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _fastchar(d=1024):
    from repro.kernels.char_kernels import behav_stats_pallas

    spec = spec_for(8)
    b = spec.n_inputs
    return (functools.partial(behav_stats_pallas, interpret=False),
            [((spec.rows, d, 4, b), jnp.int32), ((b, b), jnp.int32),
             ((b, b), jnp.float32)])


def _fastapp(d=128, m=250, k=256, n=10):
    # the mnist head at paper size: 250 test images x 256 pixels -> 10 logits
    from repro.kernels.app_kernels import table_gemv_pallas

    spec = spec_for(8)
    return (functools.partial(table_gemv_pallas, interpret=False),
            [((spec.rows, d, 4, spec.n_inputs), jnp.int32),
             ((m, k), jnp.int32), ((k, n), jnp.int32)])


def _fastmoo(p=256):
    from repro.kernels.moo_kernels import dominance_counts_pallas

    return (functools.partial(dominance_counts_pallas, interpret=False),
            [((p, 2), jnp.float32), ((p,), jnp.float32), ((p,), jnp.int32)])


def _axo(m, k=2048, n=2048, rank=1):
    from repro.kernels.axo_matmul_kernel import axo_matmul_pallas

    return (functools.partial(axo_matmul_pallas, interpret=False),
            [((m, k), jnp.float32), ((k, n), jnp.float32),
             ((rank, m, k), jnp.float32), ((rank, k, n), jnp.float32)])


def _axo_apply(m, k=2048, n=2048):
    # AxODeployment.apply at granite's decode shape: the activation's
    # quantize and code lookups in XLA, then the kernel
    from unittest import mock

    from repro.axo.deploy import AxODeployment
    from repro.kernels import ops
    from repro.launch.serve import demo_operator

    dep = AxODeployment(op=demo_operator(1), impl="pallas", layers=("attn",))

    def apply(x, bv, gb, scale):
        with mock.patch.object(ops, "on_tpu", lambda: True):
            return dep.apply(x, {"bv": bv, "gb": gb, "scale": scale})

    return (apply, [((m, k), jnp.float32), ((k, n), jnp.float32),
                    ((1, k, n), jnp.float32), ((), jnp.float32)])


CASES = {
    "fastchar.pallas-8bit-D1024": _fastchar,
    "fastapp.pallas-mnist-head": _fastapp,
    "fastmoo.pallas-P256": _fastmoo,
    "axo_matmul.pallas-decode-M8": functools.partial(_axo, 8),
    "axo_matmul.pallas-prefill-M512": functools.partial(_axo, 512),
    "axo_deploy.apply-decode-M8": functools.partial(_axo_apply, 8),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, case
