"""Driver for DSE traffic: closed-loop design-space-exploration jobs.

Set-up characterizes the configuration's training dataset on the device and
fits the estimators once (the paper fits them once per operator), then runs
one request of the cell's own shape and characterizes front-sized batches,
so every program the window runs is compiled.  The window sends requests
back to back: ``run_dse`` (one lane each) or ``run_dse_sweep`` (many lanes in
one batched dispatch).  A request started before the window's end runs to
its validated front, and the window closes when it returns.

Each lane's answer is its validated front and hypervolume.  After the window
a sample of answers drawn from the seed is checked against the plain
reference: every front config's [BEHAV, PPA] recomputed from its bits, and
the hypervolume recomputed from those objectives.
"""

from __future__ import annotations

import time
import traceback

import numpy as np

import generate
from run import Check


def settings_for(config: dict, ctx):
    from repro.core.dse import DSESettings

    s, m = config["search"], config["map"]
    return DSESettings(
        pop_size=int(s["pop_size"]), n_gen=int(s["n_gen"]),
        n_quad_grid=tuple(m["n_quad_grid"]), wt_step=float(m["wt_step"]),
        pool_size=int(m["pool_size"]),
        n_estimator_quad=int(config["estimators"]["n_estimator_quad"]),
        behav_key=config["objectives"]["behav"],
        ppa_key=config["objectives"]["ppa"], context=ctx,
    )


def setup(config: dict):
    """(spec, dataset, estimators, settings) of a configuration."""
    from repro.core.automl import fit_estimators
    from repro.core.dataset import build_training_dataset
    from repro.core.engine import ExecutionContext
    from repro.core.operator_model import spec_for

    op, data = config["operator"], config["dataset"]
    spec = spec_for(int(op["n_bits"]), op["op"], bool(op["signed"]))
    ctx = ExecutionContext(backend="jax", tuning="off")
    ds = build_training_dataset(spec, n_random=int(data["n_random"]),
                                seed=int(data["seed"]), backend=ctx)
    if len(ds.configs) != int(data["n_configs"]):
        raise RuntimeError(f"training dataset has {len(ds.configs)} configs, "
                           f"the configuration states {data['n_configs']}")
    st = settings_for(config, ctx)
    est = fit_estimators(
        ds.configs.astype(np.float64),
        {st.behav_key: ds.metrics[st.behav_key],
         st.ppa_key: ds.metrics[st.ppa_key]},
        n_quad=st.n_estimator_quad, seed=int(data["seed"]))
    return spec, ds, est, st


def serve(traffic: dict, spec, ds, est, st, request: dict, characterize_fn=None):
    """One request through the program's DSE entry; returns its lanes."""
    import dataclasses

    from repro.core.dse import run_dse, run_dse_sweep

    if traffic["entry"] == "run_dse":
        (sf,), (seed,) = request["const_sf"], request["seeds"]
        one = dataclasses.replace(st, const_sf=float(sf), seed=int(seed))
        return [run_dse(spec, ds, traffic["method"], one, estimators=est,
                        characterize_fn=characterize_fn)]
    return run_dse_sweep(spec, ds, traffic["method"], settings=st,
                         seeds=tuple(request["seeds"]),
                         const_sf_grid=tuple(request["const_sf"]),
                         estimators=est, characterize_fn=characterize_fn)


def warm_validate(spec, ds, st, max_front: int = 64) -> None:
    """Characterize every front size validation can meet (<= max_front)."""
    from repro.core.dataset import characterize

    for n in range(1, max_front + 1):
        characterize(spec, ds.configs[:n], backend=st.context)


def check_lanes(config, reference, lanes, limits) -> list[Check]:
    """Front objectives and hypervolume of each lane vs the reference."""
    front_gap = hv_gap = 0.0
    for res in lanes:
        if len(res.vpf_configs):
            ref = reference.objectives(config, res.vpf_configs)
            gap = np.abs(res.vpf_objs - ref) / np.maximum(np.abs(ref), 1e-9)
            front_gap = max(front_gap, float(gap.max()))
            hv = reference.hypervolume(ref, res.ref_point)
        else:
            hv = 0.0
        area = float(np.prod(res.ref_point))
        hv_gap = max(hv_gap, abs(res.hv_vpf - hv) / area)
    return [Check("front_gap", front_gap, float(limits["front_gap"])),
            Check("hv_gap", hv_gap, float(limits["hv_gap"]))]


def run(cell, run, reference, characterize_fn=None) -> dict:
    """Set up, measure, check.  ``characterize_fn`` replaces validation's
    characterization (the control switches on the program's sampled path)."""
    traffic, config = cell.traffic, cell.config
    with run.span("bench.setup"):
        spec, ds, est, st = setup(config)
        warm = generate.dse_request(traffic, run.seed, -1)
        serve(traffic, spec, ds, est, st, warm, characterize_fn)
        warm_validate(spec, ds, st)

    answers, timings, failed, attempted = [], [], 0, 0
    run.open_window()
    t_end = run.t_window + run.seconds
    i = 0
    while time.perf_counter() < t_end:
        req = generate.dse_request(traffic, run.seed, i)
        n_lanes = len(req["const_sf"]) * len(req["seeds"])
        attempted += n_lanes
        try:
            with run.span("bench.request"):
                lanes = serve(traffic, spec, ds, est, st, req, characterize_fn)
        except Exception:  # an answer that never comes fails the run
            traceback.print_exc()
            failed += n_lanes
            lanes = []
        answers.extend(lanes)
        timings.append({"lanes": n_lanes, **(lanes[0].timings if lanes else {})})
        i += 1
    run.close_window()

    done = len(answers)
    picked = generate.sample(done, int(traffic["check_answers"]), run.seed,
                             must=(done - 1,) if done else ())
    checks = check_lanes(config, reference, [answers[j] for j in picked],
                         traffic["limits"])
    checks.append(Check("answers_missing", float(failed), 0.0))
    return {
        "attempted": attempted, "failed": failed, "checks": checks,
        "metrics": {"dse_job_s": run.window_s / max(done, 1)},
        "layer": {"requests": timings, "jobs": done},
    }
