"""Chip smoke test: the system's two front doors, once each, on a TPU.

  python chip_smoke.py [--seed N]     # DSE phase + serving phase, one chip
  python chip_smoke.py --chips 4      # lane-sharded sweep vs one device only

Phase DSE runs the paper's signed 8x8 multiplier through the DSE pipeline on
the device engines: ``build_training_dataset`` (n_random 4000, the ``--full``
benchmark size), ``map_solution_pool`` and ``run_dse(method="map+ga")`` at
pop 256 for 20 generations.  It checks device BEHAV of 64 configs, the
accurate one among them, against the numpy oracle (integer metrics exactly),
a non-empty validated front with hv > 0, and that the Pallas BEHAV kernel
ran.  It also runs the app-BEHAV table matmul at the mnist head's shape on
the same 64 configs through the Pallas table-GEMV and checks it exactly
against the numpy product-table oracle.

Phase serve calls ``repro.launch.serve.main`` on granite-3-2b at its
published widths (d 2048, ff 8192, 40 layers, random weights from the seed):
a few exact requests, then the same prompts with the rank-1 AxO operator in
every attention projection on the Pallas ``axo_matmul``, while the ``/dse``
service answers four requests over HTTP.  It checks one full-width
projection (M=512, K=2048, N=2048) of Pallas ``axo_matmul`` against the XLA
contraction, that the deployment was traced onto the Pallas kernel, that the
AxO logit error lies in ``LOGIT_REL_ERR_BAND`` and its teacher-forced top-1
is at least ``TOP1_BOUND``, and that every ``/dse`` request came back with a
front.

``--chips 4`` runs only ``run_dse_sweep(method="ga")`` over 4 lanes with the
lanes sharded over 4 devices, and the same sweep on one device; fronts and
hypervolumes must be bit-identical.

Everything is built from the committed sources and ``--seed``: no
``experiments/cache`` dataset, tuning cache or operator library is read.
The last line of stdout is one JSON object naming the device.  Without a TPU
the script exits non-zero before any phase runs.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Phase sizes.  DSE: the paper's signed 8x8 multiplier at the ``--full``
# benchmark budget of ``benchmarks/common.py``.  Serve: granite-3-2b at its
# published widths, a few requests.  A CPU rehearsal monkeypatches these.
N_RANDOM = 4000            # random training configs
POP, GENS = 256, 20        # GA population and generations
N_PARITY = 64              # configs in the device-vs-oracle BEHAV check
APP_HEAD = (250, 256, 10)  # the mnist head at paper size: (M, K, N) codes
PROJECTION = (512, 2048, 2048)  # one full-width prefill projection (M, K, N)
LANE_DEVICES = 4           # --chips 4: lanes sharded over this many devices
LANES_N_RANDOM = 1000      # training configs of the lane-sharded sweep
SERVE_ARGV = ["--arch", "granite-3-2b", "--full-config", "--batch", "4",
              "--prompt-len", "128", "--gen", "32", "--requests", "3",
              "--axo-rank", "1", "--axo-layers", "attn", "--axo-impl", "pallas",
              "--metrics-port", "0", "--dse-smoke", "4"]

# Lower bound on the AxO teacher-forced top-1 against the exact model.  It is
# 0, for two reasons measured on the CPU at granite-3-2b's width (d 2048):
# serving's demo operator (the 1-column truncated multiplier) has a biased
# error that, summed over K=2048, outweighs a projection's signal
# (|y - x@w| / |x@w| = 1.47; 0.42 at K=256), so its top-1 is 0.0 from the
# first layer on; and random weights amplify any error with depth, so even
# the accurate operator (int8 quantization alone) falls from 0.77 at 1 layer
# to 0.03 at 4.  A wrong kernel is caught by the projection parity instead.
TOP1_BOUND = 0.0
# Band for the AxO logit error |l_axo - l| / |l| at the same seed.  Two chip
# runs gave 1.4129, about sqrt(2): the AxO logits have the exact logits'
# norm and no correlation with them.  Serving the exact path gives 0 and a
# NaN fails the band; for uncorrelated logits it admits a norm ratio of
# about 0.66 to 1.37, so a deployment whose scale is off fails too.
LOGIT_REL_ERR_BAND = (1.2, 1.7)


class _CompileClock:
    """Sums XLA backend-compile seconds reported through ``jax.monitoring``."""

    def __init__(self) -> None:
        self.secs = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration


def _run_phase(name: str, fn, clock: _CompileClock):
    import jax

    c0, t0 = clock.secs, time.perf_counter()
    out = fn()
    stats = jax.devices()[0].memory_stats() or {}
    print(f"phase {name}: wall {time.perf_counter() - t0:.1f}s "
          f"compile {clock.secs - c0:.1f}s "
          f"peak_device_bytes {stats.get('peak_bytes_in_use')}", flush=True)
    return out


def app_head_parity(spec, cfgs, seed: int, ctx) -> int:
    """The app-BEHAV table matmul on the device's default impl (the Pallas
    table-GEMV on a TPU) at the mnist head's shape, against the numpy
    product-table oracle for every config, exactly.  Returns how many times
    the Pallas kernel was dispatched."""
    import numpy as np

    from repro.apps.base import table_matmul
    from repro.apps.fastapp import table_batch, table_matmul_jax
    from repro.core.operator_model import product_tables
    from repro.obs import telemetry as obs

    m, k, n = APP_HEAD
    rng = np.random.default_rng(seed)
    a = rng.integers(0, spec.n_inputs, (m, k))
    b = rng.integers(0, spec.n_inputs, (k, n))
    n0 = obs.GLOBAL.counter("dispatch.fastapp.pallas")
    dev = np.asarray(table_matmul_jax(table_batch(spec, cfgs, ctx), a, b))
    n_kernel = obs.GLOBAL.counter("dispatch.fastapp.pallas") - n0
    if not n_kernel:
        raise AssertionError("the Pallas app table-GEMV never ran")
    for i, table in enumerate(product_tables(spec, cfgs)):
        if not np.array_equal(dev[i], table_matmul(table, a, b)):
            raise AssertionError(f"device app table matmul differs from the "
                                 f"oracle at config {i}")
    return n_kernel


def phase_dse(seed: int):
    import numpy as np

    from repro.core.dataset import build_training_dataset
    from repro.core.dse import DSESettings, map_solution_pool, run_dse
    from repro.core.engine import ExecutionContext
    from repro.core.fastchar import behav_metrics_jax
    from repro.core.metrics import behav_metrics
    from repro.core.operator_model import accurate_config, spec_for
    from repro.obs import telemetry as obs

    spec = spec_for(8)
    ctx = ExecutionContext(backend="jax", tuning="off")
    settings = DSESettings(pop_size=POP, n_gen=GENS, seed=seed, context=ctx)
    t0 = time.perf_counter()
    ds = build_training_dataset(spec, n_random=N_RANDOM, seed=seed, backend=ctx)
    t1 = time.perf_counter()
    pool = map_solution_pool(spec, ds, settings)
    t2 = time.perf_counter()
    res = run_dse(spec, ds, "map+ga", settings, map_pool=pool)
    stages = {"dataset": t1 - t0, "map_pool": t2 - t1, **res.timings}

    cfgs = np.concatenate([accurate_config(spec)[None],
                           ds.configs[: N_PARITY - 1]]).astype(np.uint8)
    dev = behav_metrics_jax(spec, cfgs, ctx=ctx)
    ref = behav_metrics(spec, cfgs)
    for key in ("AVG_ABS_ERR", "PROB_ERR", "MAX_ABS_ERR", "MSE"):
        if not np.array_equal(dev[key], ref[key]):
            raise AssertionError(f"device BEHAV {key} differs from the oracle")
    if not np.allclose(dev["AVG_ABS_REL_ERR"], ref["AVG_ABS_REL_ERR"],
                       rtol=1e-6, atol=1e-9):
        raise AssertionError("device AVG_ABS_REL_ERR differs from the oracle")
    if dev["MAX_ABS_ERR"][0] != 0:
        raise AssertionError("the accurate config shows an error")
    if not len(res.vpf_configs) or not res.hv_vpf > 0:
        raise AssertionError(f"empty validated front (hv_vpf={res.hv_vpf})")
    n_kernel = obs.GLOBAL.counter("dispatch.fastchar.pallas")
    if not n_kernel:
        raise AssertionError("the Pallas BEHAV kernel never ran")
    n_app = app_head_parity(spec, cfgs, seed, ctx)
    print(f"dse: {len(ds.configs)} training configs, pool {len(pool)}, "
          f"front {len(res.vpf_configs)}, hv_vpf {res.hv_vpf:.6g}, "
          f"fastchar.pallas dispatches {n_kernel}, BEHAV parity on "
          f"{len(cfgs)} configs, app head {APP_HEAD} exact on "
          f"fastapp.pallas ({n_app} dispatch); stage seconds "
          f"{ {k: round(v, 3) for k, v in stages.items()} }", flush=True)
    return res.hv_vpf


def projection_parity(seed: int) -> float:
    """Pallas ``axo_matmul`` vs the XLA contraction on one ``PROJECTION``,
    through ``AxODeployment.apply``; returns the max abs difference."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.axo.deploy import AxODeployment, quantize_tensor
    from repro.kernels import registry
    from repro.launch.serve import demo_operator

    m, k, n = PROJECTION
    op = demo_operator(1)
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((k, n)) / np.sqrt(k), jnp.float32)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    sv = jnp.asarray(op.signed_vals, jnp.float32)
    wq, sw = quantize_tensor(w, op.n_bits)
    entry = {"bv": sv[wq],
             "gb": jnp.moveaxis(jnp.asarray(op.g_table)[wq], -1, 0),
             "scale": sw}
    dep = AxODeployment(op=op, impl="pallas", layers=("attn",))
    y_kernel = np.asarray(dep.apply(x, entry))
    with jax.default_matmul_precision("highest"):
        y_xla = np.asarray(dataclasses.replace(dep, impl="xla").apply(x, entry))
    tol = registry.get("axo_matmul.pallas").tol
    scale = float(np.max(np.abs(y_xla))) + 1.0
    if not np.allclose(y_kernel, y_xla, rtol=tol, atol=tol * scale):
        raise AssertionError(
            f"Pallas axo_matmul vs XLA: max |diff| "
            f"{np.max(np.abs(y_kernel - y_xla)):.3g} beyond tol {tol}"
        )
    return float(np.max(np.abs(y_kernel - y_xla)))


def phase_serve(seed: int):
    from repro.launch import serve
    from repro.obs import telemetry as obs

    cache = os.path.join(ROOT, "experiments", "cache")
    os.makedirs(cache, exist_ok=True)
    library = tempfile.mkdtemp(prefix="smoke-library-", dir=cache)
    os.environ["REPRO_OPERATOR_LIBRARY"] = library
    n0 = obs.GLOBAL.counter("dispatch.axo_apply.pallas")
    try:
        report = serve.main(SERVE_ARGV + ["--seed", str(seed)])
    finally:
        del os.environ["REPRO_OPERATOR_LIBRARY"]
        shutil.rmtree(library, ignore_errors=True)
    n_kernel = obs.GLOBAL.counter("dispatch.axo_apply.pallas") - n0

    axo = report["axo"]
    lo, hi = LOGIT_REL_ERR_BAND
    if not n_kernel:
        raise AssertionError("the AxO deployment never reached Pallas axo_matmul")
    if not lo <= axo["logit_rel_err"] <= hi:
        raise AssertionError(f"AxO logit rel_err {axo['logit_rel_err']} "
                             f"outside [{lo}, {hi}]")
    if not axo["top1"] >= TOP1_BOUND:
        raise AssertionError(f"AxO top-1 {axo['top1']} below {TOP1_BOUND}")
    if len(report["dse"]) != 4 or not all(r["hv_vpf"] > 0 for r in report["dse"]):
        raise AssertionError(f"/dse smoke: {report['dse']}")
    diff = projection_parity(seed)
    print(f"serve: {report['arch']} exact prefill {report['prefill_s']:.3f}s "
          f"decode {report['decode_s']:.3f}s; axo top1 {axo['top1']:.4f} "
          f"free-run match {axo['free_run_match']:.4f} "
          f"logit rel_err {axo['logit_rel_err']:.4f} "
          f"(axo_apply.pallas traced {n_kernel}x); projection {PROJECTION} "
          f"pallas-vs-xla max|diff| {diff:.3g}; /dse answered "
          f"{len(report['dse'])} requests", flush=True)
    return axo["top1"]


def phase_lanes(seed: int):
    """Lane-sharded ``run_dse_sweep`` over ``LANE_DEVICES`` vs one device."""
    import numpy as np

    from repro.core.dataset import build_training_dataset
    from repro.core.dse import DSESettings, run_dse_sweep
    from repro.core.engine import ExecutionContext
    from repro.core.operator_model import spec_for

    spec = spec_for(8)
    one = ExecutionContext(backend="jax", tuning="off")
    ds = build_training_dataset(spec, n_random=LANES_N_RANDOM, seed=seed,
                                backend=one)
    grid = dict(seeds=(seed, seed + 1), const_sf_grid=(0.5, 1.0))

    def sweep(ctx):
        st = DSESettings(pop_size=POP, n_gen=GENS, seed=seed, context=ctx)
        return run_dse_sweep(spec, ds, "ga", settings=st, **grid)

    base = sweep(one)
    sharded = sweep(ExecutionContext(backend="jax", tuning="off",
                                     n_devices=LANE_DEVICES,
                                     shard_axes=("lanes",)))
    for i, (a, b) in enumerate(zip(base, sharded)):
        if not (np.array_equal(a.vpf_configs, b.vpf_configs)
                and np.array_equal(a.vpf_objs, b.vpf_objs)
                and a.hv_vpf == b.hv_vpf and a.hv_ppf == b.hv_ppf):
            raise AssertionError(f"lane {i}: sharded sweep differs "
                                 f"(hv {a.hv_vpf} vs {b.hv_vpf})")
    if len(base) != len(sharded) or not all(r.hv_vpf > 0 for r in base):
        raise AssertionError("sweep lost lanes or found empty fronts")
    print(f"lanes: {len(base)} lanes over {LANE_DEVICES} devices "
          f"bit-identical to one device; hv_vpf "
          f"{[float(r.hv_vpf) for r in base]}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the lane-sharded sweep across 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = _CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    if args.chips == 4:
        _run_phase("lanes", lambda: phase_lanes(args.seed), clock)
    else:
        _run_phase("dse", lambda: phase_dse(args.seed), clock)
        _run_phase("serve", lambda: phase_serve(args.seed), clock)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
