"""The benchmark's design: every configuration, cell and metric built so far,
one JSON file each under ``data/design/<kind>/<name>.json``, in the format
of ``BENCHMARK.json``, which lists those of them proven on the chip.

A metric's design file holds no ``workloads``: ``BENCHMARK.json`` says which
listed cells report it, and a cell that is not listed says what it reports
in its own file (``reports``).  So a new configuration, cell or metric is a
new design file, and listing one is an entry in ``BENCHMARK.json``.

The checks take the root of a checkout, so they can run on a copy.
"""

from __future__ import annotations

import json
from pathlib import Path

KINDS = ("configs", "workloads", "end_to_end", "per_layer")
METRICS = ("end_to_end", "per_layer")


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def entries(root: Path) -> dict:
    """kind -> name -> design entry, each read from its own file."""
    out = {}
    for kind in KINDS:
        out[kind] = {}
        for p in sorted((root / "bench/tests/data/design" / kind).glob("*.json")):
            entry = _json(p)
            assert p.name == f"{entry['name']}.json", p
            out[kind][entry["name"]] = entry
    return out


def document(root: Path) -> dict:
    """Every design entry as one document in the format of BENCHMARK.json:
    a metric reports in the listed cells that BENCHMARK.json names for it
    (all of them where it names none) and in each unlisted cell whose
    ``reports`` name it."""
    bench = _json(root / "BENCHMARK.json")
    design = entries(root)
    listed = {w["name"] for w in bench["workloads"]}
    doc = {"configs": list(design["configs"].values()),
           "workloads": [{k: v for k, v in w.items() if k != "reports"}
                         for w in design["workloads"].values()]}
    for key in METRICS:
        given = {m["name"]: m for m in bench[key]}
        doc[key] = []
        for name, m in design[key].items():
            m = dict(m)
            more = [w for w, e in design["workloads"].items()
                    if w not in listed and name in e.get("reports", ())]
            if name not in given:
                m["workloads"] = more
            elif "workloads" in given[name]:
                m["workloads"] = given[name]["workloads"] + more
            doc[key].append(m)
    return doc


def check_files_used(root: Path) -> None:
    """Every file under bench/configs, traffic, metrics, models and
    reference belongs to a design entry."""
    bench, design = root / "bench", entries(root)
    configs = design["configs"].values()
    assert {f"bench/configs/{p.name}" for p in (bench / "configs").glob("*")} \
        == {c["file"] for c in configs}
    assert {p.stem for p in (bench / "traffic").glob("*.json")} \
        == {w["traffic"] for w in design["workloads"].values()}
    assert {p.stem for p in (bench / "metrics").glob("*.py")} \
        == set(design["per_layer"])
    assert {p.stem for p in (bench / "reference").glob("*.py")} == set(design["configs"])
    families = {_json(root / c["file"]).get("model_type") for c in configs}
    assert {p.stem for p in (bench / "models").glob("*.py")} == families - {None}


def check_listed(root: Path) -> None:
    """Each cell, configuration and metric in BENCHMARK.json is its design
    entry; a metric names only listed cells."""
    bench, design = _json(root / "BENCHMARK.json"), entries(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells
    for name, w in cells.items():
        assert {k: v for k, v in design["workloads"][name].items()
                if k != "reports"} == w, name
    configs = {c["name"]: c for c in bench["configs"]}
    assert set(configs) == {w["config"] for w in cells.values()}
    assert all(design["configs"][n] == c for n, c in configs.items())
    for key in METRICS:
        for m in bench[key]:
            want = {k: v for k, v in m.items() if k not in ("bound", "workloads")}
            assert design[key][m["name"]] == want, m["name"]
            assert set(m.get("workloads", cells)) <= set(cells), m["name"]


def check_reports(root: Path) -> None:
    """Every cell of the design, listed or not, reports setup_s, another
    end-to-end metric and a per-layer metric, and each of its per-layer
    metrics moves one of its end-to-end metrics."""
    doc, design = document(root), entries(root)
    for w in design["workloads"].values():
        assert set(w.get("reports", ())) <= set(design["end_to_end"]) | set(design["per_layer"])
    names = [w["name"] for w in doc["workloads"]]
    for cell in names:
        got = {key: {m["name"]: m for m in doc[key]
                     if cell in m.get("workloads", names)} for key in METRICS}
        e2e = set(got["end_to_end"])
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert got["per_layer"], cell
        for m in got["per_layer"].values():
            assert m["moves"] in e2e, (cell, m["name"])


def check(root: Path) -> None:
    check_files_used(root)
    check_listed(root)
    check_reports(root)
