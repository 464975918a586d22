"""The benchmark's own tests: CPU only, at tiny sizes.  Nothing here touches
a TPU at import; runs are driven through ``run.run_cell``, which leaves the
look for a chip to ``run.main``."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@pytest.fixture
def tiny_cell():
    """make(kind, **traffic overrides) -> a Cell at a CPU-sized configuration.
    DSE cells carry the limits of the real cell they stand for; the tiny
    serving model has limits of its own (``serve_tiny.json``), since its
    logits lie closer together than the real model's."""
    import design_checks
    import run

    design = design_checks.document(BENCH.parent)

    def make(kind: str, trace: bool = False, **over):
        real, config, reference = {
            "dse": ("mapga_jobs", "mul4s_tiny.json", "mul8s.py"),
            "sweep": ("ga_sweep", "mul4s_tiny.json", "mul8s.py"),
            "axo": ("axo_decode", "granite-tiny.json", "granite-3-2b.py"),
            "exact": ("exact_decode", "granite-tiny.json", "granite-3-2b.py"),
        }[kind]
        traffic = _json(BENCH / "traffic" / f"{real}.json")
        if kind in ("axo", "exact"):
            # the tiny model's own limits, set from its own readings
            tiny = _json(DATA / "serve_tiny.json")
            tiny["axo"] = traffic["axo"]
            traffic = tiny
        if kind in ("dse", "sweep"):
            # the tiny operator's tightest constraints leave empty fronts
            traffic["const_sf_grid"] = [1.0, 1.5]
        traffic.update(over)
        name = {w["traffic"]: w["name"] for w in design["workloads"]}[real]
        cell = run.resolve_cell(name, design)
        return run.Cell(name=name, chips=1, config=_json(DATA / config),
                        traffic=traffic, end_to_end=cell.end_to_end,
                        per_layer=cell.per_layer,
                        reference=BENCH / "reference" / reference)

    return make


@pytest.fixture
def v5e_peaks(monkeypatch):
    """Let a traced CPU run read the v5e peak table (its device kind is
    'cpu', which the table rightly refuses)."""
    import counts

    real = counts.peaks_for
    monkeypatch.setattr(counts, "peaks_for", lambda kind: real("TPU v5 lite"))
