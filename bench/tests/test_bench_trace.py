"""The trace reduction on a small trace recorded on a v5e: a jitted step
that calls the AxO Pallas kernel once, run three times inside ``bench.decode``
annotations with host sleeps between them (see the trace's own events)."""

from pathlib import Path

import pytest

import trace_reduce

TRACE = Path(__file__).resolve().parent / "data" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_trace(TRACE)


def test_one_device_busy_less_than_the_window(summary):
    assert summary.n_devices == 1
    assert 0 < summary.busy_s < summary.window_s
    assert summary.idle_s == pytest.approx(summary.window_s - summary.busy_s)


def test_programs_and_the_named_kernel(summary):
    assert summary.module_counts == {"jit_decode_step": 3}
    assert len(summary.module_events) == 3
    # the Pallas call itself, not the fusion that reads its output
    assert summary.kernel_count("axo_matmul") == 3
    assert 0 < summary.kernel_s("axo_matmul") < summary.modules["jit_decode_step"]
    assert summary.kernel_count("no_such_kernel") == 0


def test_idle_gaps_are_named_by_host_spans(summary):
    by_span = summary.gaps_by_span()
    assert by_span.get("bench.decode", 0) + by_span.get("bench.batch", 0) \
        > 0.9 * sum(by_span.values())
    assert sum(s for s, _ in summary.gaps) == pytest.approx(summary.idle_s, rel=1e-6)


def test_breakdown_is_short_and_leaves_out_containers(summary):
    b = summary.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert not any(name.startswith("%while") for name, _ in b["device_ops"])
    assert all(sec > 0 for _, sec in b["device_ops"] + b["idle_gaps"])


def test_union_merges_overlaps():
    covered, merged = trace_reduce._union_ns([(0, 10), (5, 12), (20, 30)])
    assert covered == 22 and merged == [[0, 12], [20, 30]]
