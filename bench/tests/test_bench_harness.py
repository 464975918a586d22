"""The harness finds everything by name, draws traffic from the seed alone,
refuses a machine without a chip, and prints the result line the contract
asks for.  ``data/design/`` holds every cell built so far, one file an entry
(``design_checks.py``); ``BENCHMARK.json`` lists those of them proven on the
chip.  A configuration, model family, traffic, cell, reference, metric or
named scope is added as new files and entries alone."""

import hashlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import design_checks
import generate
import run

BENCH = Path(run.__file__).resolve().parent
ROOT = BENCH.parent
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
DESIGN = design_checks.document(ROOT)
CELLS = [w["name"] for w in DESIGN["workloads"]]
TRAFFIC = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_every_file_by_name(name):
    cell = run.resolve_cell(name, DESIGN)
    assert cell.driver.is_file() and cell.reference.is_file()
    assert cell.config["name"] == next(
        w["config"] for w in DESIGN["workloads"] if w["name"] == name)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        reader = run.load_module(BENCH / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_every_metric_and_config_file_is_used():
    design_checks.check_files_used(ROOT)


def test_benchmark_lists_cells_of_the_design_whole():
    """Each cell, configuration and metric in BENCHMARK.json is the design's
    entry; a metric names only listed cells; every cell reports setup_s,
    another end-to-end metric and a per-layer metric that moves one of
    them."""
    design_checks.check_listed(ROOT)
    design_checks.check_reports(ROOT)


@pytest.mark.parametrize("name", TRAFFIC)
def test_traffic_is_a_pure_function_of_the_seed(name):
    traffic = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    seed = 2**31 + 12345   # larger than 32 signed bits hold
    if traffic["kind"] == "dse":
        draw = [generate.dse_request(traffic, seed, i) for i in range(4)]
        assert draw == [generate.dse_request(traffic, seed, i) for i in range(4)]
        assert draw != [generate.dse_request(traffic, seed + 1, i) for i in range(4)]
        # every seed does the same set of constraint problems
        other = [generate.dse_request(traffic, 7, i) for i in range(4)]
        assert [d["const_sf"] for d in draw] == [d["const_sf"] for d in other]
        assert all(0 <= s < 2**31 for d in draw for s in d["seeds"])
    else:
        a = generate.prompts(traffic, 49155, seed, 3)
        assert a.shape == (traffic["batch"], traffic["prompt_len"])
        assert np.array_equal(a, generate.prompts(traffic, 49155, seed, 3))
        assert not np.array_equal(a, generate.prompts(traffic, 49155, seed + 1, 3))
        assert not np.array_equal(a, generate.prompts(traffic, 49155, seed, 4))
    assert generate.sample(10, 3, seed, must=(9,)) == generate.sample(10, 3, seed, must=(9,))
    assert 9 in generate.sample(10, 3, seed, must=(9,))


def test_run_without_a_chip_exits_nonzero_before_setup(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         BENCHMARK["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize("kind,trace", [("dse", False), ("sweep", True),
                                        ("exact", False), ("axo", True)])
def test_result_line_has_the_contract_keys(tiny_cell, v5e_peaks, kind, trace):
    cell = tiny_cell(kind)
    res = run.run_cell(cell, 2**33 + 1, 0.5, trace)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    if trace:
        keys.append("breakdown")
    assert list(res) == keys + ["checks"]      # the numbers compared come last
    json.dumps(res)
    assert res["correct"] is True and res["attempted"] > 0 and res["failed"] == 0
    dev = res["device"]
    assert set(dev) >= {"platform", "kind", "count", "memory_peak_bytes",
                        "memory_window_bytes"}
    if trace:
        assert "busy_s" in dev and "window_s" in dev
        assert set(res["metrics"]) <= {m["name"] for m in cell.per_layer}
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert all(m["value"] > 0 for m in res["metrics"].values())


TOY_FAMILY = '''"""A toy family: a fused qkv projection, attention in every second layer."""


def served_model(config):
    return ("toy", config["num_hidden_layers"]), {}


def reference_weights(params, cfg):
    return params


def shapes(config):
    d, f, n = config["hidden_size"], config["intermediate_size"], config["num_hidden_layers"]
    attn = {"attn": {"wqkv": (d, 3 * d), "wo": (d, d)}}
    mlp = {"mlp": {"w_up": (d, f), "w_down": (f, d)}}
    return {"d_model": d, "vocab": config["vocab_size"], "attn_layers": n // 2,
            "layers": [dict(attn, **mlp) if i % 2 else mlp for i in range(n)]}
'''


def _add(root: Path, rel: str, content) -> None:
    path = root / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(content if isinstance(content, str)
                    else json.dumps(content, indent=1) + "\n")


def _load(path: Path, name: str, monkeypatch):
    """Import a file of the copy under its own name (its dataclasses look
    their module up in sys.modules)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, name, mod)
    spec.loader.exec_module(mod)
    return mod


def _digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_family_config_cell_and_metric_are_new_files_alone(tmp_path, monkeypatch):
    """On a copy of the benchmark: a model family, a configuration, a
    traffic mix, a cell, a plain reference, a per-layer reader and a named
    scope are added as new files and BENCHMARK.json entries; the design
    checks pass and the cell resolves, and no file that was there changes."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digests(tmp_path)
    del before["BENCHMARK.json"]          # new entries go into it

    config = {"name": "toy-4l", "model_type": "toy", "hidden_size": 64,
              "intermediate_size": 256, "num_hidden_layers": 4, "vocab_size": 100,
              "initializer_range": 0.02}
    cell = {"name": "toy.decode", "config": "toy-4l", "traffic": "toy_decode",
            "chips": 1, "why": "a toy family's decode"}
    spec = {"name": "toy_scope_ms", "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "toy layer",
            "moves": "output_tok_per_s"}
    entry = {"name": "toy-4l", "source": "https://example.org/toy",
             "file": "bench/configs/toy-4l.json", "reduced": [], "why": "toy"}
    _add(tmp_path, "bench/models/toy.py", TOY_FAMILY)
    _add(tmp_path, "bench/configs/toy-4l.json", config)
    _add(tmp_path, "bench/traffic/toy_decode.json",
         dict(json.loads((BENCH / "traffic" / "exact_decode.json").read_text()),
              batch=2, prompt_len=4, gen=3))
    _add(tmp_path, "bench/reference/toy-4l.py",
         '"""The toy family\'s plain reference."""\n\n\n'
         'def served_logits(config, weights, prompts, tokens, **kw):\n'
         '    raise NotImplementedError\n')
    _add(tmp_path, "bench/metrics/toy_scope_ms.py",
         'def read(ctx):\n    return ctx["layer"].get("toy_ms")\n')
    _add(tmp_path, "bench/scope_names/toy.state.txt", "the toy family's state update\n")
    _add(tmp_path, "bench/tests/data/design/configs/toy-4l.json", entry)
    _add(tmp_path, "bench/tests/data/design/workloads/toy.decode.json", cell)
    _add(tmp_path, "bench/tests/data/design/per_layer/toy_scope_ms.json", spec)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append(entry)
    bench["workloads"].append(cell)
    bench["per_layer"].append(dict(spec, workloads=["toy.decode"]))
    for m in bench["end_to_end"]:
        if m["name"] == "output_tok_per_s":
            m["workloads"].append("toy.decode")
    _add(tmp_path, "BENCHMARK.json", bench)

    design_checks.check(tmp_path)
    monkeypatch.setattr(sys, "path", list(sys.path))   # the copy's run.py adds to it
    harness = _load(tmp_path / "bench" / "run.py", "bench_copy_run", monkeypatch)
    got = harness.resolve_cell("toy.decode")
    assert got.config == config and got.traffic["gen"] == 3
    assert got.reference == tmp_path / "bench" / "reference" / "toy-4l.py"
    assert {m["name"] for m in got.end_to_end} == {"output_tok_per_s", "setup_s"}
    assert [m["name"] for m in got.per_layer] == ["toy_scope_ms"]
    reader = harness.load_module(tmp_path / "bench" / "metrics" / "toy_scope_ms.py")
    assert reader.read({"layer": {"toy_ms": 1.5}}) == 1.5

    serve = harness.load_module(got.driver, "bench_copy_serve")
    assert serve.served_model(config) == (("toy", 4), {})
    model = serve.family(config).shapes(config)
    counts = harness.load_module(tmp_path / "bench" / "counts.py", "bench_copy_counts")
    per_token = 2 * (4 * (64 * 256 * 2) + 2 * (64 * 192 + 64 * 64) + 64 * 100)
    assert counts.model_flops(model, 1, 0) == per_token
    assert counts.model_flops(model, 1, 10) - per_token == 2 * 2 * 2 * 10 * 64
    assert counts.axo_calls(model, ("attn",), 2, 4, 3) == [(8, 64, 192), (8, 64, 64)] * 2 \
        + [(2, 64, 192), (2, 64, 64)] * 4
    scopes = _load(tmp_path / "bench" / "scopes.py", "bench_copy_scopes", monkeypatch)
    assert scopes.scope_of("jit(decode_step)/layers/toy.state/add") == "toy.state"

    after = _digests(tmp_path)
    assert {k: after.get(k) for k in before} == before


def test_unknown_model_type_names_the_file_it_looked_for(tmp_path):
    serve = run.load_module(BENCH / "drivers" / "serve.py")
    with pytest.raises(ValueError, match=r"models/no_such_family\.py"):
        serve.served_model({"model_type": "no_such_family"})
