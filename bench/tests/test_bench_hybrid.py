"""granite-4.0-h-micro's family (``bench/models/granitemoehybrid.py``) and
reference (``bench/reference/granite-4.0-h-micro.py``): the served model and
its folds, the projection shapes the counts read, and the comparison that
decides ``correct``, run on the CPU at a tiny hybrid size (its own
configuration and limits, ``data/hybrid-tiny.json`` and
``data/hybrid_serve_tiny.json``).  The program passes; fp8, a token
altered, an SSM state not carried between decode steps and a recurrence
dropped (the D skip kept) fail."""

import json

import jax
import jax.numpy as jnp
import pytest

import control
import counts
import design_checks
import run

DATA = run.BENCH / "tests" / "data"
CELL = "granite4h.axo.decode"
SEED = 2**32 + 77
CONFIG = json.loads((run.BENCH / "configs" / "granite-4.0-h-micro.json").read_text())
TRAFFIC = json.loads((run.BENCH / "traffic" / "hybrid_axo_decode.json").read_text())
FAMILY = run.load_module(run.BENCH / "models" / "granitemoehybrid.py")
MODEL = FAMILY.shapes(CONFIG)


def _tiny(axo: bool) -> run.Cell:
    real = run.resolve_cell(CELL, design_checks.document(run.ROOT))
    traffic = json.loads((DATA / "hybrid_serve_tiny.json").read_text())
    traffic["axo"] = TRAFFIC["axo"] if axo else None
    return run.Cell(name=CELL, chips=1,
                    config=json.loads((DATA / "hybrid-tiny.json").read_text()),
                    traffic=traffic, end_to_end=real.end_to_end,
                    per_layer=real.per_layer, reference=real.reference)


def _checks(res):
    return {k: v["value"] for k, v in res["checks"].items()}


@pytest.mark.parametrize("axo", [False, True], ids=["exact", "axo"])
def test_program_passes_and_fp8_control_fails(axo):
    cell = _tiny(axo)
    good = run.run_cell(cell, SEED, 0.2, False)
    assert good["correct"], good["checks"]
    bad = run.run_cell(cell, SEED, 0.2, False, driver=control.control_driver(cell))
    assert not bad["correct"], bad["checks"]


def _ssm_state_not_carried(cell):
    """Each decode step hands back the new cache with every Mamba layer's
    SSM state as it was given (the KV cache and conv window carried)."""
    drv = run.load_module(cell.driver)

    class Stale(drv.Server):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            decode = self.decode

            def step(p, cache, *x):
                logits, new = decode(p, cache, *x)
                return logits, jax.tree_util.tree_map_with_path(
                    lambda path, n, o: o if path[-1].key == "state" else n,
                    new, cache)

            self.decode = step

    drv.Server = Stale
    return drv


def test_a_token_altered_fails():
    cell = _tiny(True)
    res = run.run_cell(cell, SEED, 0.2, False,
                       driver=control.fault_driver(cell, "token_altered"))
    limit = cell.traffic["limits"]["logit_gap"]
    assert not res["correct"] and _checks(res)["logit_gap"] > limit, res["checks"]


def test_an_ssm_state_not_carried_fails():
    cell = _tiny(True)
    res = run.run_cell(cell, SEED, 0.2, False, driver=_ssm_state_not_carried(cell))
    assert not res["correct"], res["checks"]


def test_a_recurrence_dropped_fails(monkeypatch):
    """The SSM's y without the state's readout, in prefill and in decode:
    only the D skip carries x.  With the conv and A drawn as the
    configuration states, the recurrence is most of a Mamba layer's
    output, so the check sees it gone."""
    from repro.models import ssm

    chunked, step = ssm.ssd_chunked, ssm.ssd_step

    def no_readout(fn):
        def run_(*a, **k):
            y, state = fn(*a, **k)
            return jnp.zeros_like(y), state
        return run_

    monkeypatch.setattr(ssm, "ssd_chunked", no_readout(chunked))
    monkeypatch.setattr(ssm, "ssd_step", no_readout(step))
    res = run.run_cell(_tiny(True), SEED, 0.2, False)
    assert not res["correct"], res["checks"]


def test_served_model_folds_the_multipliers_and_the_ssm_draw():
    cfg, fold = FAMILY.served_model(CONFIG)
    (stage,) = cfg.stages
    assert stage.repeats == 4
    assert stage.layers == (("mamba", "dense"),) * 5 + (("attn", "dense"),) \
        + (("mamba", "dense"),) * 4
    assert cfg.n_layers == 40 and cfg.pos_encoding == "none"
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.d_ff, cfg.vocab) == \
        (2048, 32, 8, 8192, 100352)
    s = cfg.ssm
    assert (s.d_state, s.d_conv, s.expand, s.head_dim, s.n_groups, s.chunk) == \
        (128, 4, 2, 64, 1, 256)
    # the residual norms take the fold; the gated norm keeps the published eps
    assert cfg.norm_eps == pytest.approx(1e-5 / 0.22**2)
    assert s.norm_eps == 1e-5
    assert fold == {"tok": pytest.approx(12 / 0.22),
                    "norm_f": pytest.approx(0.22 / 96), "wq": 0.125,
                    "conv_w": 32.0, "a_log": -2.0}


@pytest.mark.parametrize("change,match", [
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"num_local_experts": 8}, "num_local_experts"),
    ({"layer_types": ["mamba"] * 39 + ["attention"]}, "does not repeat"),
    ({"num_hidden_layers": 36}, "every layer"),
])
def test_served_model_refuses_what_the_program_does_not_run(change, match):
    with pytest.raises(ValueError, match=match):
        FAMILY.served_model(dict(CONFIG, **change))


def test_shapes_give_36_ssm_and_4_attention_layers():
    groups = [tuple(sorted(layer)) for layer in MODEL["layers"]]
    assert groups.count(("mlp", "ssm")) == 36 and groups.count(("attn", "mlp")) == 4
    assert MODEL["attn_layers"] == 4
    assert [i for i, g in enumerate(groups) if "attn" in g] == [5, 15, 25, 35]
    mamba = MODEL["layers"][0]
    assert mamba["ssm"] == {"in_proj": (2048, 8512), "out_proj": (4096, 2048)}
    # 36 x 76152832 + 4 x 60817408 + the tied head 2048 x 100352
    assert counts.model_flops(MODEL, 1, 0) == 2 * 3_190_292_480


def test_counts_of_the_cells_batches():
    b, p, g = TRAFFIC["batch"], TRAFFIC["prompt_len"], TRAFFIC["gen"]
    prefill = (b * p * 2 * 2_984_771_584 + b * 4 * 4 * 2048 * (p * (p + 1) // 2)
               + b * 2 * 2048 * 100352)
    decode = sum(b * (2 * 3_190_292_480 + 4 * 4 * 2048 * (p + i + 1))
                 for i in range(g - 1))
    assert counts.serve_flops(MODEL, b, p, g) == prefill + decode == 8_017_500_176_384
    layer = {"mamba": [(2048, 8512), (4096, 2048)],
             "attention": [(2048, 2048), (2048, 512), (2048, 512), (2048, 2048)]}
    step = [kn for t in CONFIG["layer_types"] for kn in layer[t]]
    calls = counts.axo_calls(MODEL, tuple(TRAFFIC["axo"]["layers"]), b, p, g)
    assert calls == [(m, k, n) for m in [256] + [8] * 127 for k, n in step]
    assert len(calls) == 128 * 88
