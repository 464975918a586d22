"""The harness finds everything by name, draws traffic from the seed alone,
refuses a machine without a chip, and prints the result line the contract
asks for.  ``data/design.json`` holds every cell built so far in the
format of ``BENCHMARK.json``, which lists those of them proven on the chip."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import generate
import run

BENCH = Path(run.__file__).resolve().parent
BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
DESIGN_FILE = BENCH / "tests" / "data" / "design.json"
DESIGN = json.loads(DESIGN_FILE.read_text())
CELLS = [w["name"] for w in DESIGN["workloads"]]
TRAFFIC = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_every_file_by_name(name):
    cell = run.resolve_cell(name, DESIGN_FILE)
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
    names = {m["name"] for m in DESIGN["per_layer"]}
    assert {p.stem for p in (BENCH / "metrics").glob("*.py")} == names
    files = {c["file"] for c in DESIGN["configs"]}
    assert {f"bench/configs/{p.name}" for p in (BENCH / "configs").glob("*")} == files
    used = {w["traffic"] for w in DESIGN["workloads"]}
    assert set(TRAFFIC) == used


def test_benchmark_lists_cells_of_the_design_whole():
    """Each cell, configuration and metric in BENCHMARK.json is the design's
    entry; a metric names only listed cells; every listed cell reports
    setup_s, another end-to-end metric and a per-layer metric."""
    def by_name(doc, key):
        return {e["name"]: e for e in doc[key]}

    cells = by_name(BENCHMARK, "workloads")
    assert cells and all(by_name(DESIGN, "workloads")[n] == w for n, w in cells.items())
    used = {w["config"] for w in cells.values()}
    assert set(by_name(BENCHMARK, "configs")) == used
    assert all(by_name(DESIGN, "configs")[n] == c
               for n, c in by_name(BENCHMARK, "configs").items())
    reported = {n: set() for n in cells}
    for key in ("end_to_end", "per_layer"):
        design = by_name(DESIGN, key)
        for name, m in by_name(BENCHMARK, key).items():
            want = {k: v for k, v in m.items() if k not in ("bound", "workloads")}
            assert {k: v for k, v in design[name].items() if k != "workloads"} == want
            listed = m.get("workloads", list(cells))
            assert set(listed) <= set(cells) & set(design[name].get("workloads", cells))
            for n in listed:
                reported[n].add((key, name))
    for n, got in reported.items():
        e2e = {name for key, name in got if key == "end_to_end"}
        assert "setup_s" in e2e and len(e2e) >= 2, n
        assert any(key == "per_layer" for key, _ in got), n


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
