"""Contracts for the observability analysis layer (PR 8).

  * **Regression sentinel** (``repro.obs.regress``): suite verdicts are
    PASS / REGRESSED / IMPROVED / NEW / SKIPPED; wall-clock moves gate only
    beyond the noise band (max of a relative floor and a multiple of the
    trial IQR); quality metrics (hv, top-1) parsed from the rows' derived
    strings gate with relative tolerance and always hard-fail; the CLI
    writes a machine-readable verdict and exits non-zero iff REGRESSED.
  * **History store**: appends never overwrite; ``latest`` is chronological.
  * **Prometheus exposition** (``repro.obs.prom``): counters render as
    ``_total``, histograms as summaries with quantile labels, names are
    sanitized to the Prometheus charset; ``/metrics`` + ``/healthz`` round-
    trip over real HTTP against the live telemetry.
"""

import json
import pathlib
import re
import urllib.request

import numpy as np
import pytest

from repro.obs import regress
from repro.obs import telemetry as tm
from repro.obs.prom import MetricsServer, health_payload, render_prometheus


# ---------------------------------------------------------------------------
# Fixtures: synthetic bench reports
# ---------------------------------------------------------------------------


def _suite(median, iqr=0.01, rows=()):
    return {
        "wall_s": median, "wall_s_min": median * 0.97,
        "wall_s_median": median, "wall_s_iqr": iqr,
        "repeats": 3, "rows": list(rows),
    }


def _report(suites, sha="abc1234"):
    return {
        "timestamp_utc": "2026-08-08T00:00:00Z", "git_sha": sha,
        "device": "cpu:cpux1", "quick": True, "seed": 0,
        "suites": suites,
    }


def _dse_row(hv_ppf, hv_vpf):
    return {"name": "dse.fig12_sf0.5_ga", "us_per_call": 1e6,
            "derived": f"hv_ppf={hv_ppf:.5g} hv_vpf={hv_vpf:.5g} evals=1344"}


def _serving_row(top1, match):
    return {"name": "serving.axo_t1_r8_b4", "us_per_call": 1e6,
            "derived": f"12.3 tok/s match={match:.2f} top1={top1:.2f} rel=0.0123"}


# ---------------------------------------------------------------------------
# Metric parsing + wall stats
# ---------------------------------------------------------------------------


def test_parse_metrics_extracts_numeric_tokens():
    m = regress.parse_metrics("hv_ppf=0.5 hv_vpf=4.5e-2 evals=1000 note=fast")
    assert m == {"hv_ppf": 0.5, "hv_vpf": 4.5e-2, "evals": 1000.0}
    # bare numbers and non-strings are ignored, not crashes
    assert regress.parse_metrics("12.3 tok/s match=0.98") == {"match": 0.98}
    assert regress.parse_metrics(None) == {}
    assert regress.parse_metrics("") == {}


def test_wall_stats_min_median_iqr():
    s = regress.wall_stats([3.0, 1.0, 2.0])
    assert s["wall_s_min"] == 1.0
    assert s["wall_s_median"] == 2.0 == s["wall_s"]
    assert s["wall_s_iqr"] == pytest.approx(1.0)
    assert s["repeats"] == 3
    # single trial: zero IQR, median = the trial
    s1 = regress.wall_stats([5.0])
    assert s1["wall_s_median"] == 5.0 and s1["wall_s_iqr"] == 0.0
    assert regress.wall_stats([])["repeats"] == 0


# ---------------------------------------------------------------------------
# Verdicts
# ---------------------------------------------------------------------------


def test_verdicts_pass_regressed_improved_new_skipped():
    base = _report({
        "same": _suite(1.0), "slow": _suite(1.0), "fast": _suite(1.0),
        "gone": _suite(1.0),
    })
    cand = _report({
        "same": _suite(1.01), "slow": _suite(2.0), "fast": _suite(0.4),
        "fresh": _suite(1.0),
    }, sha="def5678")
    v = regress.compare(base, cand)
    assert v["suites"]["same"]["status"] == "PASS"
    assert v["suites"]["slow"]["status"] == "REGRESSED"
    assert v["suites"]["fast"]["status"] == "IMPROVED"
    assert v["suites"]["fresh"]["status"] == "NEW"
    assert v["suites"]["gone"]["status"] == "SKIPPED"
    assert v["overall"] == "REGRESSED"
    assert any("slow" in f for f in v["failures"])
    # NEW and IMPROVED do not fail the run
    v2 = regress.compare(
        _report({"fast": _suite(1.0)}), _report({"fast": _suite(0.4)})
    )
    assert v2["overall"] == "PASS"


def test_noise_band_scales_with_iqr():
    # a 40% move on a noisy suite (IQR ~ the move) is NOT a regression...
    base = _report({"noisy": _suite(1.0, iqr=0.2)})
    cand = _report({"noisy": _suite(1.4, iqr=0.2)})
    v = regress.compare(base, cand, wall_rel=0.25, iqr_mult=3.0)
    assert v["suites"]["noisy"]["status"] == "PASS"
    assert v["suites"]["noisy"]["wall"]["band_s"] == pytest.approx(0.6)
    # ...but the same move on a tight suite is
    v2 = regress.compare(
        _report({"tight": _suite(1.0, iqr=0.01)}),
        _report({"tight": _suite(1.4, iqr=0.01)}),
    )
    assert v2["suites"]["tight"]["status"] == "REGRESSED"
    # the candidate's own noise widens the band too (max of the two IQRs)
    v3 = regress.compare(
        _report({"s": _suite(1.0, iqr=0.01)}),
        _report({"s": _suite(1.4, iqr=0.2)}),
    )
    assert v3["suites"]["s"]["status"] == "PASS"


def test_quality_gate_hv_and_top1():
    base = _report({
        "dse": _suite(1.0, rows=[_dse_row(0.5, 0.4)]),
        "serving": _suite(1.0, rows=[_serving_row(0.97, 0.9)]),
    })
    # hv drop beyond 2% -> REGRESSED even though wall is identical
    cand = _report({
        "dse": _suite(1.0, rows=[_dse_row(0.5, 0.3)]),
        "serving": _suite(1.0, rows=[_serving_row(0.97, 0.9)]),
    })
    v = regress.compare(base, cand)
    assert v["suites"]["dse"]["status"] == "REGRESSED"
    assert v["suites"]["serving"]["status"] == "PASS"
    checks = {c["metric"]: c["status"] for c in v["suites"]["dse"]["quality"]}
    assert checks["hv_vpf"] == "REGRESSED" and checks["hv_ppf"] == "PASS"
    # top1 is a higher-better gate: a drop regresses, a rise improves
    cand2 = _report({
        "dse": _suite(1.0, rows=[_dse_row(0.5, 0.4)]),
        "serving": _suite(1.0, rows=[_serving_row(0.80, 0.9)]),
    })
    v2 = regress.compare(base, cand2)
    assert v2["suites"]["serving"]["status"] == "REGRESSED"
    assert v2["overall"] == "REGRESSED"
    # within-tolerance wiggle passes (2% on hv, 5% on top1)
    cand3 = _report({
        "dse": _suite(1.0, rows=[_dse_row(0.5, 0.396)]),
        "serving": _suite(1.0, rows=[_serving_row(0.95, 0.9)]),
    })
    assert regress.compare(base, cand3)["overall"] == "PASS"


def test_wall_warn_only_demotes_wall_but_not_quality():
    base = _report({
        "slow": _suite(1.0),
        "dse": _suite(1.0, rows=[_dse_row(0.5, 0.4)]),
    })
    cand = _report({
        "slow": _suite(3.0),
        "dse": _suite(1.0, rows=[_dse_row(0.5, 0.2)]),
    })
    v = regress.compare(base, cand, wall_warn_only=True)
    # the wall regression is reported but only warns...
    assert v["suites"]["slow"]["status"] == "REGRESSED"
    assert any("slow" in w for w in v["warnings"])
    assert not any("slow" in f for f in v["failures"])
    # ...while the hv regression still hard-fails
    assert v["overall"] == "REGRESSED"
    assert any("hv_vpf" in f for f in v["failures"])
    # with only the wall regression, warn-only means overall PASS
    v2 = regress.compare(
        _report({"slow": _suite(1.0)}), _report({"slow": _suite(3.0)}),
        wall_warn_only=True,
    )
    assert v2["overall"] == "PASS" and v2["warnings"]


def test_failed_candidate_suite_regresses():
    base = _report({"s": _suite(1.0)})
    cand = _report({"s": {"wall_s": 0.1, "failed": True}})
    v = regress.compare(base, cand)
    assert v["suites"]["s"]["status"] == "REGRESSED"
    assert v["overall"] == "REGRESSED"
    # a failed BASELINE suite cannot gate anything: candidate counts as NEW
    v2 = regress.compare(cand, base)
    assert v2["suites"]["s"]["status"] == "NEW"
    assert v2["overall"] == "PASS"


def test_pre_repeats_reports_still_compare():
    # PR 7 reports had only single-shot wall_s: zero-IQR fallback applies
    old = _report({"s": {"wall_s": 1.0, "rows": []}})
    new = _report({"s": _suite(1.1)})
    v = regress.compare(old, new)
    assert v["suites"]["s"]["status"] == "PASS"
    assert v["suites"]["s"]["wall"]["baseline_s"] == 1.0


# ---------------------------------------------------------------------------
# History store + CLI
# ---------------------------------------------------------------------------


def test_history_append_and_latest(tmp_path):
    d = str(tmp_path / "hist")
    assert regress.latest_report(d) is None
    p1 = regress.append_history(_report({"s": _suite(1.0)}), d)
    p2 = regress.append_history(_report({"s": _suite(2.0)}), d)
    assert p1 != p2
    latest = regress.latest_report(d)
    assert latest == sorted([p1, p2])[-1]
    rep = regress.load_report(latest)
    assert "suites" in rep
    with pytest.raises(ValueError):
        bad = tmp_path / "bad.json"
        bad.write_text("{}")
        regress.load_report(str(bad))


def test_cli_verdict_roundtrip_and_exit_codes(tmp_path, capsys):
    base_p = tmp_path / "baseline.json"
    hist = str(tmp_path / "hist")
    base_p.write_text(json.dumps(_report({
        "dse": _suite(1.0, rows=[_dse_row(0.5, 0.4)]),
    })))
    # green: identical candidate via the history store's "latest"
    regress.append_history(_report({
        "dse": _suite(1.02, rows=[_dse_row(0.5, 0.4)]),
    }), hist)
    out = tmp_path / "verdict.json"
    rc = regress.main([
        "--baseline", str(base_p), "--candidate", "latest",
        "--history-dir", hist, "--out", str(out), "--wall-warn-only",
    ])
    assert rc == 0
    v = json.loads(out.read_text())
    assert v["overall"] == "PASS"
    assert v["suites"]["dse"]["status"] == "PASS"
    assert v["candidate"]["path"].startswith(hist)
    capsys.readouterr()

    # red: inject a synthetic hv regression (the CI sentinel's red-path check)
    regress.append_history(_report({
        "dse": _suite(1.0, rows=[_dse_row(0.5, 0.2)]),
    }), hist)
    rc = regress.main([
        "--baseline", str(base_p), "--candidate", "latest",
        "--history-dir", hist, "--out", str(out), "--wall-warn-only",
    ])
    assert rc == 1
    v = json.loads(out.read_text())
    assert v["overall"] == "REGRESSED" and v["failures"]
    captured = capsys.readouterr()
    assert "REGRESSED" in captured.out

    # empty history is a usage error, not a pass
    assert regress.main([
        "--baseline", str(base_p), "--history-dir", str(tmp_path / "empty"),
    ]) == 2


def test_committed_baseline_is_a_valid_report():
    import os

    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "benchmarks", "baselines", "cpu-smoke.json")
    rep = regress.load_report(path)
    assert rep["quick"] is True
    assert rep["suites"], "baseline must contain at least one suite"
    for name, entry in rep["suites"].items():
        assert "wall_s_median" in entry, name
        assert entry.get("repeats", 0) >= 3, name
    # the baseline must carry gated quality metrics for hv and top-1
    joined = json.dumps(rep)
    assert "hv_vpf=" in joined and "top1=" in joined
    # comparing the baseline against itself is a clean PASS
    v = regress.compare(rep, rep)
    assert v["overall"] == "PASS"
    assert all(s["status"] == "PASS" for s in v["suites"].values())


# ---------------------------------------------------------------------------
# Prometheus exposition + /metrics + /healthz
# ---------------------------------------------------------------------------


def test_render_prometheus_format():
    tel = tm.Telemetry("t")
    tel.count("serve.requests", 3)
    tel.gauge("serve.tokens_per_s", 123.5)
    tel.gauge("axo_matmul.pad_waste", 0.25)
    for x in range(100):
        tel.observe("serve.decode_step_ms", float(x))
    tel.observe("serve.tokens_per_s", 123.5)  # gauge/hist name collision
    text = render_prometheus(tel)

    assert "# TYPE repro_serve_requests_total counter" in text
    assert "repro_serve_requests_total 3" in text
    assert "# TYPE repro_axo_matmul_pad_waste gauge" in text
    # summary with quantile labels + count/sum
    assert '# TYPE repro_serve_decode_step_ms summary' in text
    assert 'repro_serve_decode_step_ms{quantile="0.5"}' in text
    assert 'repro_serve_decode_step_ms{quantile="0.99"}' in text
    assert "repro_serve_decode_step_ms_count 100" in text
    # collision: summary keeps the base name, gauge moves to _last
    assert "# TYPE repro_serve_tokens_per_s_last gauge" in text
    assert "# TYPE repro_serve_tokens_per_s summary" in text
    # every sample line is name[{labels}] value -- no empty values
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        assert name and float(value) == float(value)  # parses, NaN-safe


def test_render_prometheus_sanitizes_names():
    tel = tm.Telemetry("t")
    tel.count("jit.retrace.fastmoo.run")
    tel.gauge("weird-name with spaces", 1.0)
    text = render_prometheus(tel)
    assert "repro_jit_retrace_fastmoo_run_total 1" in text
    assert "repro_weird_name_with_spaces 1.0" in text


def test_metrics_and_healthz_http_roundtrip():
    tel = tm.Telemetry("serve-test")
    tel.count("serve.requests", 2)
    tel.observe("serve.prefill_ms", 12.0)
    with MetricsServer(tel=tel, port=0, check_device=False) as srv:
        assert srv.port != 0  # ephemeral port resolved
        r = urllib.request.urlopen(f"{srv.url}/metrics")
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain; version=0.0.4")
        body = r.read().decode()
        assert "repro_serve_requests_total 2" in body

        # a request recorded AFTER start is visible on the next scrape
        tel.count("serve.requests", 5)
        body = urllib.request.urlopen(f"{srv.url}/metrics").read().decode()
        assert "repro_serve_requests_total 7" in body

        h = urllib.request.urlopen(f"{srv.url}/healthz")
        assert h.status == 200
        payload = json.loads(h.read().decode())
        assert payload["status"] == "ok"
        assert payload["deployment"] == {"mode": "exact"}
        assert payload["tuning_cache"]["ok"] is True
        assert payload["requests"] == 7

        srv.set_deployment({"mode": "axo", "rank": 8})
        payload = json.loads(
            urllib.request.urlopen(f"{srv.url}/healthz").read().decode()
        )
        assert payload["deployment"]["rank"] == 8

        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{srv.url}/nope")


def test_healthz_device_liveness_real_probe():
    # with the real device check on, the CPU backend must report ok
    payload = health_payload(check_device=True)
    assert payload["status"] == "ok"
    assert payload["device"]["status"] == "ok"
    assert payload["device"]["count"] >= 1


# ---------------------------------------------------------------------------
# Committed alerting rules (launch/alerts.yml)
# ---------------------------------------------------------------------------


_ALERTS_PATH = pathlib.Path(__file__).resolve().parents[1] / "launch" / "alerts.yml"
_DURATION_RE = re.compile(r"^\d+(ms|s|m|h|d|w|y)$")


def _load_alert_groups():
    text = _ALERTS_PATH.read_text()
    try:
        import yaml
    except ImportError:
        # structural fallback: the committed file is plain block YAML, so a
        # minimal indentation walk recovers the rule dicts we assert on
        groups, rule = [], None
        for line in text.splitlines():
            s = line.strip()
            if s.startswith("- name:"):
                groups.append({"name": s.split(":", 1)[1].strip(), "rules": []})
            elif s.startswith("- alert:"):
                rule = {"alert": s.split(":", 1)[1].strip()}
                groups[-1]["rules"].append(rule)
            elif rule is not None and s.startswith(
                ("expr:", "for:", "severity:", "summary:", "description:")
            ):
                k, v = s.split(":", 1)
                if k == "severity":
                    rule.setdefault("labels", {})[k] = v.strip()
                elif k in ("summary", "description"):
                    # block scalars (>-) read as a truthy marker -- enough
                    # for the presence assertions
                    rule.setdefault("annotations", {})[k] = v.strip() or ">-"
                else:
                    rule[k] = v.strip()
        return groups
    doc = yaml.safe_load(text)
    assert isinstance(doc, dict) and "groups" in doc
    return doc["groups"]


def test_alert_rules_syntax():
    """Prometheus rule-file shape: groups -> rules, each with alert/expr/for,
    a severity label, and both annotations."""
    groups = _load_alert_groups()
    assert len(groups) >= 2
    n_rules = 0
    for g in groups:
        assert g["name"].startswith("repro_serve")
        for r in g["rules"]:
            n_rules += 1
            assert re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", r["alert"])
            assert r["expr"].strip()
            assert _DURATION_RE.match(str(r["for"]))
            assert r["labels"]["severity"] in ("warning", "critical")
            ann = r.get("annotations", {})
            assert ann.get("summary") and ann.get("description")
    assert n_rules >= 6


def test_alert_rules_reference_live_exposition_names():
    """Every repro_* metric an alert expression references must be a name the
    serving telemetry actually exposes through render_prometheus."""
    tel = tm.Telemetry("serve-alerts")
    # the exact series launch/serve.py records (see its tel.* call sites)
    tel.count("serve.requests")
    tel.observe("serve.prefill_ms", 1.0)
    tel.observe("serve.decode_step_ms", 1.0)
    tel.gauge("serve.tokens_per_s", 1.0)
    tel.observe("serve.tokens_per_s", 1.0)
    tel.gauge("serve.axo_top1", 1.0)
    tel.gauge("serve.axo_free_run_match", 1.0)
    tel.gauge("serve.axo_logit_rel_err", 0.0)
    # the exact series the DSE service records (repro.service.store / .queue)
    tel.count("service.store_hit")
    tel.count("service.store_miss")
    tel.count("service.store_corrupt")
    tel.count("service.request_hit")
    tel.count("service.request_miss")
    tel.count("service.jobs")
    tel.count("service.batches")
    tel.count("service.job_errors")
    tel.gauge("service.library_size", 1.0)
    tel.gauge("service.front_count", 1.0)
    tel.observe("service.queue_depth", 1.0)
    tel.observe("service.batch_lanes", 1.0)
    exposed = {
        line.split("{", 1)[0].split(" ")[0]
        for line in render_prometheus(tel).splitlines()
        if line and not line.startswith("#")
    }

    referenced = set()
    for g in _load_alert_groups():
        for r in g["rules"]:
            referenced |= set(re.findall(r"\brepro_[a-z0-9_]+", str(r["expr"])))
    assert referenced  # the rules do gate repro_* metrics
    missing = referenced - exposed
    assert not missing, f"alert rules reference unexposed metrics: {missing}"
