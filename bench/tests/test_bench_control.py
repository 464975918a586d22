"""The comparison that decides ``correct`` fails what it must: the control
(the precision below the stated one, or the program's sampled
characterization) and each fault a cell can have, planted in the timed path
of a CPU run at a tiny size.  The sound program passes at the same size."""

import dataclasses
import json

import numpy as np
import pytest

import control
import run

SEED = 2**32 + 77


def _checks(res):
    return {k: v["value"] for k, v in res["checks"].items()}


@pytest.mark.parametrize("kind", ["dse", "sweep"])
def test_dse_program_passes_and_sampled_control_fails(tiny_cell, kind):
    cell = tiny_cell(kind)
    good = run.run_cell(cell, SEED, 0.2, False)
    assert good["correct"], good["checks"]
    bad = run.run_cell(cell, SEED, 0.2, False, driver=control.control_driver(cell))
    assert not bad["correct"], bad["checks"]
    assert _checks(bad)["front_gap"] > 10 * _checks(good)["front_gap"]


def test_dse_answer_altered_where_produced_fails(tiny_cell, monkeypatch):
    """A validated objective off by 1% where characterization produces it."""
    from repro.core import dse

    cell = tiny_cell("dse")
    real = dse._default_characterize

    def skewed(spec, settings):
        fn = real(spec, settings)
        return lambda configs: fn(configs) * np.array([1.01, 1.0])

    monkeypatch.setattr(dse, "_default_characterize", skewed)
    res = run.run_cell(cell, SEED, 0.2, False)
    assert not res["correct"] and _checks(res)["front_gap"] > 1e-3


def test_dse_hypervolume_altered_where_produced_fails(tiny_cell, monkeypatch):
    from repro.core import dse

    cell = tiny_cell("sweep")
    real = dse.hypervolume_2d
    monkeypatch.setattr(dse, "hypervolume_2d", lambda pts, ref: 1.01 * real(pts, ref))
    res = run.run_cell(cell, SEED, 0.2, False)
    assert not res["correct"] and _checks(res)["hv_gap"] > 1e-4


@pytest.mark.parametrize("kind", ["exact", "axo"])
def test_serve_program_passes_and_fp8_control_fails(tiny_cell, kind):
    cell = tiny_cell(kind)
    good = run.run_cell(cell, SEED, 0.2, False)
    assert good["correct"], good["checks"]
    bad = run.run_cell(cell, SEED, 0.2, False, driver=control.control_driver(cell))
    assert not bad["correct"], bad["checks"]


@pytest.mark.parametrize("kind", ["exact", "axo"])
@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_serve_faults_fail(tiny_cell, fault, kind):
    cell = tiny_cell(kind)
    res = run.run_cell(cell, SEED, 0.2, False,
                       driver=control.fault_driver(cell, fault))
    assert not res["correct"], res["checks"]


def test_granite_multipliers_fold_into_the_served_weights():
    """The published multipliers become weight factors and a norm eps; the
    query factor is a power of two, so its 8-bit codes are unchanged."""
    cfg_file = json.loads((run.BENCH / "configs" / "granite-3-2b.json").read_text())
    drv = run.load_module(run.BENCH / "drivers" / "serve.py")
    cfg, fold = drv.served_model(cfg_file)
    assert fold["wq"] == 0.125
    assert fold["tok"] == pytest.approx(12.0 / 0.22)
    assert fold["norm_f"] == pytest.approx(0.22 / 96.0)
    assert cfg.norm_eps == pytest.approx(1e-5 / 0.22**2)


@pytest.mark.parametrize("kind", ["exact", "axo"])
def test_serving_without_the_multipliers_fails(tiny_cell, kind):
    """The program's decoder with the multipliers left out is another model:
    the comparison with the reference, which applies them, must fail it."""
    cell = tiny_cell(kind)
    drv = run.load_module(cell.driver)
    folded = drv.served_model
    drv.served_model = lambda config: (dataclasses.replace(
        folded(config)[0], norm_eps=float(config["rms_norm_eps"])), {})
    res = run.run_cell(cell, SEED, 0.2, False, driver=drv)
    assert not res["correct"], res["checks"]
